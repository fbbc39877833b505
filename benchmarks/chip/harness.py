"""One run of one cell: load, warm up, measure, check, and the result line.

Everything a cell is made of is found by name from `BENCHMARK.json`:
the configuration's file (`configs[].file`), the traffic mix
(`traffic/<name>.json`, read by `traffic.generate`), each end-to-end metric
(`e2e/<name>.py`) and each per-layer metric (`metrics/<name>.py`), and the
configuration's plain reference (`reference/<name>.py`). A new cell, mix,
configuration or metric is a new file and a new entry, never an edit here.
A metric module defines `read(ctx)`, which returns a number or None where it
finds nothing to read; a None leaves the metric out of the line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------
def load_spec(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_of(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def config_of(spec: dict, name: str, root: Path = ROOT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((root / entry["file"]).read_text())


def mix_of(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def module(kind: str, name: str, bench: Path = BENCH):
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, trace: bool):
    """The cell's end-to-end metrics (trace 0) or per-layer metrics (trace 1)."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in mine)]


# ---------------------------------------------------------------------------
# the configuration, for the engine and for the benchmark's own arithmetic
# ---------------------------------------------------------------------------
def dims_of(cfg: dict):
    from counts import Dims
    heads = cfg["num_attention_heads"]
    return Dims(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        window=cfg.get("sliding_window"),
    )


def program_config(name: str, cfg: dict):
    from repro.configs.base import AttnSpec, ModelConfig
    m = dims_of(cfg)
    if cfg.get("tie_word_embeddings") or cfg.get("reference") != "dense_gqa":
        raise ValueError(f"{name}: the harness builds dense GQA decoders with an untied head")
    return ModelConfig(
        name=name, family="dense", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        vocab_size=m.vocab, norm_eps=cfg["rms_norm_eps"],
        attn=AttnSpec(kind="swa" if m.window else "full", window=m.window,
                      rope_theta=float(cfg["rope_theta"])),
    )


# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts programs compiled, or fetched from the persistent cache, while on."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def build(name: str, cfg: dict, mix: dict, devices, seed: int):
    """The engine over `devices`, with the benchmark's seeded weights."""
    import jax
    import jax.numpy as jnp
    import traffic
    import weights
    from repro.models import model_param_defs
    from repro.models.params import is_def
    from repro.parallel.sharding import make_exec_config
    from repro.serving.engine import EngineConfig, ServingEngine

    sv = cfg["serving"]
    mcfg = program_config(name, cfg)
    tps = traffic.tps(mix)
    if max(tps) > len(devices):
        raise ValueError(f"mix switches to TP {max(tps)} on {len(devices)} devices")
    econf = EngineConfig(
        candidate_tps=tuple(tps), n_slots=sv["slots_per_chip"] * len(devices),
        max_len=sv["max_len"], prefill_buckets=tuple(sv["prefill_buckets"]),
        dtype=jnp.dtype(sv["dtype"]),
    )
    with jax.default_device(devices[0]):
        params = weights.engine_params(weights.base_key(seed), dims_of(cfg), mcfg.vocab_padded)
    want = jax.tree_util.tree_map(lambda d: d.shape, model_param_defs(mcfg, make_exec_config(mcfg, 1)),
                                  is_leaf=is_def)
    got = jax.tree_util.tree_map(lambda a: a.shape, params)
    if want != got:
        raise ValueError(f"weights do not match the engine's tree: {got} vs {want}")
    eng = ServingEngine(mcfg, params, devices, econf)
    del params
    return eng


def run_cell(spec: dict, cell: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float, root: Path = ROOT, bench: Path = BENCH, hooks=None,
             keep_trace=None, control=False) -> dict:
    """One run; returns the result line's object. `hooks(engine)`, where
    given, may replace parts of the engine (the fault tests do)."""
    import jax
    import check
    import traffic
    from peaks import peaks_for
    from driver import Driver
    from repro.launch.serve import enable_compile_cache
    from repro.serving.request import Request

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = config_of(spec, cell["config"], root)
    mix = mix_of(cell["traffic"], bench)
    m = dims_of(cfg)
    sv = cfg["serving"]
    counter = CompileCounter()
    eng = build(cell["config"], cfg, mix, devices, seed)
    if hooks:
        hooks(eng)
    log(f"warmup {eng.warmup():.3f} s over TPs {eng.tps}")
    if traffic.tp_at(mix, 0.0) not in (None, eng.tp):
        eng.switch_tp(traffic.tp_at(mix, 0.0))
    reqs = traffic.generate(mix, seconds, seed, m.vocab, sv["max_len"])
    drv = Driver(eng, reqs, lambda r: Request(r.req_id, r.tier, r.prompt, r.max_new),
                 lambda t: traffic.tp_at(mix, t))
    if mix["arrivals"] == "batch":
        drv.fill()
    trace_dir = root / ".bench_out" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # every Python call would be an event: the loop slows
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.on = True
    setup_s = time.perf_counter() - t_start
    drv.run_window(seconds)
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    end_s = drv.drain(mix["drain_s"]) if mix["arrivals"] == "open" else drv.now()
    log(f"programs compiled or loaded inside the window: {counter.n}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    due = [r for r in drv.records.values() if r.due_s < seconds]
    summary = SimpleNamespace(
        records=due, seconds=seconds, end_s=end_s, setup_s=setup_s,
        tiers=mix.get("tiers", {}), calls=drv.calls,
    )
    ctx = None
    if trace:
        import tracereduce
        ev = tracereduce.load(str(trace_dir), {d.id for d in devices})
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: ops per device {[len(v) for v in ev['devices'].values()]}, "
            f"host spans {len(ev['host'])}")
        if keep_trace:
            tracereduce.save(ev, keep_trace)
        try:
            reduced = tracereduce.Reduced(ev)
        except ValueError as e:  # no device ops: the CPU, in tests
            log(f"trace: {e}")
            reduced = None
        ctx = SimpleNamespace(calls=drv.calls, dims=m, chips=len(devices), seconds=seconds,
                              peaks=peaks_for(devices[0].device_kind) if reduced else None,
                              trace=reduced)
    metrics = {}
    for entry in metrics_for(spec, cell["name"], trace):
        mod = module("metrics" if trace else "e2e", entry["name"], bench)
        v = mod.read(ctx if trace else summary)
        if v is not None:
            metrics[entry["name"]] = {"value": float(v), "unit": entry["unit"]}

    # -- correctness, after the window and with the engine freed ----------
    switch_s = [s for s, _, _, _ in drv.calls.switches if 0 <= s <= end_s]
    chk = cfg["check"]
    sample = check.sample(drv.finished, drv.records, switch_s, seed,
                          chk["min_tokens"], chk["min_seqs"])
    finished_n = len(drv.finished)
    del eng, drv
    gc.collect()
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    if sample:
        gap, n_tok, ref = check.widest_gap(seed, m, eps, theta, sample, devices[0])
    else:
        gap, n_tok, ref = math.inf, 0, None
    correct = bool(n_tok > 0 and gap <= check.GAP_LIMIT)
    log(f"checked {len(sample)} requests, {n_tok} served tokens "
        f"(longest {max((len(r.generated) for r in sample), default=0)})")
    d0 = devices[0]
    result = {
        "correct": correct,
        "attempted": len(due) if mix["arrivals"] == "open" else sum(r.tokens > 0 for r in due),
        "failed": sum(not r.finished for r in due) if mix["arrivals"] == "open" else 0,
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
                   "memory_peak_bytes": int(peak)},
    }
    if control and ref is not None:  # the float8 reference in the program's place
        gap_c, _, _ = check.widest_gap(seed, m, eps, theta, sample, devices[0], "fp8", ref)
        result["control"] = {"correct": bool(gap_c <= check.GAP_LIMIT), "widest_logit_gap": gap_c}
        log(f"control (float8 reference) widest_logit_gap {gap_c} limit {check.GAP_LIMIT}")
    if trace and ctx.trace:
        result["device"].update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(), "idle_gaps": ctx.trace.idle_gaps()}
    result["check"] = {"widest_logit_gap": {"value": gap if math.isfinite(gap) else None,
                                            "limit": check.GAP_LIMIT},
                       "tokens_compared": {"value": n_tok, "limit": 1}}
    log(f"finished {finished_n}; due in window {len(due)}")
    log(f"check widest_logit_gap {gap} limit {check.GAP_LIMIT}")
    log(f"check tokens_compared {n_tok} limit >= 1")
    return result
