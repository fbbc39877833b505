"""Where a cell's time goes, by the engine's own spans and named scopes: one
traced window of a cell, reduced by `enginetrace`, printed as one JSON line.

    python3 benchmarks/chip/breakdown.py --workload <cell> --seed <n> --seconds <s>

The window is served as `run.py --trace 1` serves it (the same set-up,
driver and spans). After it, the engine's programs are compiled again (from
the persistent cache where it holds them) to read each instruction's
`op_name`. The line holds `tokens_per_s` with tracing on; the cell's
per-layer metrics from `BENCHMARK.json`, computed on the extended
reduction; the engine's own readings (`decode_host_gap_ms`,
`logits_fetch_ms`, the decode program's self time per step under each
scope); the mean of each `engine.*` span; the window's idle time by driver
and engine span; device ops by self time as `program/scope/op`; idle gaps
labelled by engine span; the largest `unscoped` ops, and any op no compiled
text named (`?`). It checks no output (`run.py` does). Exits 2 where JAX
finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import enginetrace  # noqa: E402
import harness  # noqa: E402
import readers  # noqa: E402

DECODE = "jit_decode_step"


def program_texts(eng) -> list:
    """Compiled HLO text of the engine's decode, prefill (per bucket) and
    cache-insert programs at every TP, at the shapes and shardings it runs."""
    import jax
    import numpy as np

    def like(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings)

    n = eng.econf.n_slots
    texts = []
    for tp in eng.tps:
        storage = like(eng.storage, eng.store.storage_shardings(eng.meshes[tp]))
        caches = like(eng.slots.arrays, eng._cache_shardings(tp))
        texts.append(eng._decode_fns[tp].lower(
            storage, caches, np.zeros((n, 1), np.int32), np.zeros((n,), np.int32)).compile().as_text())
        for L in eng.econf.prefill_buckets:
            pre = eng._prefill_fns[tp].lower(storage, np.zeros((1, L), np.int32), 1).compile()
            texts.append(pre.as_text())
            seq = jax.tree_util.tree_map(
                lambda o, s: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=s),
                pre.out_info[2], pre.output_shardings[2])
            texts.append(eng._insert_fn.lower(caches, seq, 0).compile().as_text())
    return texts


def engine_readings(ctx) -> dict:
    """The engine-level numbers of a traced window (ms; per decode step where
    the name says decode)."""
    tr = ctx.trace
    steps = len(readers.steps(ctx))

    def per_step(seconds):
        return 1e3 * seconds / steps if steps else None

    out = {
        "steps": steps,
        "decode_host_gap_ms": per_step(enginetrace.idle_in(tr, "step")),
        "logits_fetch_ms": tr.span_ms("engine.logits"),
        "decode_attention_ms": per_step(tr.scope_s(DECODE, ("attention", "kv_write"))),
        "decode_mlp_ms": per_step(tr.scope_s(DECODE, ("mlp",))),
    }
    for scope in ("kv_write", "select", "embed", "head", enginetrace.UNSCOPED):
        out[f"decode_{scope}_ms"] = per_step(tr.scope_s(DECODE, (scope,)))
    return out


def measure(spec: dict, cell: dict, seed: int, seconds: float, devices,
            root=harness.ROOT, bench=harness.BENCH, keep=None) -> dict:
    """One traced window of `cell` on `devices`; `keep`, where given, is a
    path that receives the trace's events (gzipped JSON)."""
    import jax

    import tracereduce
    import traffic
    from driver import Driver
    from peaks import peaks_for
    from repro.launch.serve import enable_compile_cache
    from repro.serving.engine import SCOPES, SPANS
    from repro.serving.request import Request

    harness.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = harness.config_of(spec, cell["config"], root)
    mix = harness.mix_of(cell["traffic"], bench)
    arch, eng = harness.build(cell["config"], cfg, mix, devices, seed, bench)
    m = arch.dims(cfg)
    harness.log(f"warmup {eng.warmup():.3f} s over TPs {eng.tps}")
    if traffic.tp_at(mix, 0.0) not in (None, eng.tp):
        eng.switch_tp(traffic.tp_at(mix, 0.0))
    reqs = traffic.generate(mix, seconds, seed, m.vocab, cfg["serving"]["max_len"])
    drv = Driver(eng, reqs, lambda r: Request(r.req_id, r.tier, r.prompt, r.max_new),
                 lambda t: traffic.tp_at(mix, t))
    if mix["arrivals"] == "batch":
        drv.fill()
    trace_dir = root / ".bench_out" / "breakdown"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    drv.run_window(seconds)
    jax.profiler.stop_trace()
    op_scopes = enginetrace.scopes_from_hlo(program_texts(eng), SCOPES)
    ev = enginetrace.load(str(trace_dir), {d.id for d in devices}, op_scopes, SCOPES)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if keep:
        tracereduce.save(ev, keep)
    tr = enginetrace.EngineReduced(ev)
    d0 = devices[0]
    ctx = SimpleNamespace(calls=drv.calls, arch=arch, dims=m, chips=len(devices), seconds=seconds,
                          peaks=peaks_for(d0.device_kind) if d0.platform == "tpu" else None,
                          trace=tr)
    due = [r for r in drv.records.values() if r.due_s < seconds]
    metrics = {"tokens_per_s": harness.module("e2e", "tokens_per_s", bench).read(
        SimpleNamespace(records=due, seconds=seconds))}
    for entry in harness.metrics_for(spec, cell["name"], True):
        v = harness.module("metrics", entry["name"], bench).read(ctx)
        if v is not None:
            metrics[entry["name"]] = float(v)
    return {
        "workload": cell["name"], "seed": seed,
        "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
                   "busy_s": tr.busy_s, "window_s": tr.window_s},
        "metrics": metrics,
        "engine": engine_readings(ctx),
        "spans_ms": {n: tr.span_ms(n) for n in SPANS if tr.span_ms(n) is not None},
        "programs": {p: tr.program_s(p) for p in sorted({n.split("/")[0] for n in tr.self_s})},
        "idle_by_span": tr.idle_by_span(),
        "breakdown": {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps(),
                      "unscoped": tr.top_ops(10, enginetrace.UNSCOPED),
                      "unknown": tr.top_ops(10, enginetrace.UNKNOWN)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = harness.load_spec(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.cell_of(spec, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"breakdown.py: {cell['name']} needs {cell['chips']} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    print(json.dumps(measure(spec, cell, args.seed, args.seconds, devices[: cell["chips"]])),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
