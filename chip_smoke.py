"""Chip smoke: serve h2o-danube-1.8b at its published widths on a TPU
through the engine's normal path, and check what it served.

    python3 chip_smoke.py              # one chip, TP 1
    python3 chip_smoke.py --chips 4    # four chips: TP 1->2->4->1 mid-decode
                                       # against fixed TP 1 on the same chips

Weights are random (seeded), in bf16; the cache is bf16, 16 slots x 2048
tokens; prompts of 32-480 tokens are prefilled in buckets of 128 and 512.
Every logit the engine produced is checked against a no-cache forward pass
over the prompt plus the generated tokens (teacher-forced), with float32
activations at "highest" matmul precision, on the same chip, from the same
seeded bf16 weights. The last line of stdout is a JSON object naming the
device; it says "ok" only when every phase passed. Without a TPU the script
exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from dataclasses import replace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Teacher-forced check: for every generated position, the largest absolute
# logit difference over the vocab, divided by the largest absolute reference
# logit there, must stay below LOGIT_TOL; a non-finite logit fails it. On
# the CPU, at danube's depth (24 layers) and reduced width (d_model 256),
# the bf16 engine deviated from this reference by at most 0.024, and a
# forward pass whose weights were rounded to 8 bits (float8 e4m3) by at
# least 0.098 at every position. The tolerance sits between the two, with
# room for the TPU's own rounding of float32 matmuls at default precision.
# tests/test_chip_smoke.py keeps both sides of it: the engine passes, and
# float8-rounded reference weights fail.
LOGIT_TOL = 0.05

PROMPT_LENS = (32, 480)
MAX_NEW = 32
N_REQUESTS = 24
SEED = 0
SWITCH_EVERY = 8  # decode steps between switches: 1->2->4->1 inside the first wave


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _reference_fn(cfg):
    import jax

    from repro.models import forward
    from repro.models.model import logits_for
    from repro.parallel.sharding import DEFAULT_RULES, make_exec_config

    ec = make_exec_config(cfg, 1)

    @jax.jit
    def ref(params, tokens):
        h, _, _ = forward(params, cfg, ec, rules=DEFAULT_RULES, mesh=None,
                          tokens=tokens, mode="train")
        return logits_for(params, cfg, h, DEFAULT_RULES, None)[0, :, : cfg.vocab_size]

    return ref


def check_rel_dev(got, want, what: str) -> float:
    """max over positions of max|got - want| / max|want| across the vocab.
    Raises when a logit or the deviation is not finite, so that a NaN
    cannot drop out of a maximum and pass the tolerance."""
    import numpy as np

    check(np.isfinite(got).all() and np.isfinite(want).all(), f"{what}: non-finite logits")
    dev = float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())
    check(np.isfinite(dev), f"{what}: non-finite deviation")
    return dev


def reference_params(cfg, device, seed, dtype):
    """The engine's seeded `dtype` weights on `device`, for the reference.
    Only the embedding table is upcast: it makes every activation float32,
    and each matmul upcasts the other weights one layer at a time, so the
    reference holds no float32 copy of the whole model."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import model_param_defs
    from repro.models.params import init_params
    from repro.parallel.sharding import make_exec_config

    with jax.default_device(device):
        defs = model_param_defs(cfg, make_exec_config(cfg, 1))
        params = init_params(defs, jax.random.PRNGKey(seed), dtype)
        params["embed"] = params["embed"].astype(jnp.float32)
    return params


def teacher_forced_deviation(cfg, device, params, done, logit_trace, pad_to):
    """Max over every request and generated position of the relative logit
    deviation (`check_rel_dev`) between the engine and a no-cache forward
    pass over prompt plus generated tokens with `params`."""
    import jax
    import numpy as np

    ref = _reference_fn(cfg)
    worst = 0.0
    for r in done:
        got = np.stack(logit_trace[r.req_id]).astype(np.float32)  # (G, V)
        check(len(got) == len(r.generated),
              f"request {r.req_id}: {len(got)} logit rows for {len(r.generated)} tokens")
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
        tokens = np.zeros((1, pad_to), np.int32)
        tokens[0, : len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(params, jax.device_put(tokens, device)))
        want = want[r.prompt_len - 1 : r.prompt_len - 1 + len(got)]
        worst = max(worst, check_rel_dev(got, want, f"request {r.req_id}"))
    return worst


def serve_and_check(cfg, devices, econf, *, n_requests=N_REQUESTS, max_new=MAX_NEW,
                    prompt_lens=PROMPT_LENS, seed=SEED, switch_every=SWITCH_EVERY):
    """Serve seeded requests through ServingEngine on `devices`, then check
    every logit against the teacher-forced reference on devices[0].
    Returns {"generated", "logits", "max_dev", "switch_log", "peak_bytes"};
    raises RuntimeError when a deviation reaches LOGIT_TOL."""
    import gc

    import numpy as np

    from repro.configs.base import ceil_to
    from repro.launch.serve import build_engine, make_requests, serve, switch_schedule

    eng = build_engine(cfg, devices, replace(econf, record_logits=True), seed)
    print(f"engine on {len(devices)} x {devices[0].device_kind}, TPs {eng.tps}")
    print(f"warmup (compile) {eng.warmup():.3f} s")
    reqs = make_requests(cfg, n_requests, max_new, seed, prompt_lens)
    rep = serve(eng, reqs, switch_schedule(eng.tps, switch_every))
    for line in rep.lines():
        print(line)
    check(len(rep.done) == n_requests, f"{len(rep.done)} of {n_requests} requests served")
    stats = devices[0].memory_stats()
    peak = stats["peak_bytes_in_use"] if stats else None
    print(f"device 0 peak_bytes_in_use after serving: {peak}")
    out = {
        "generated": {r.req_id: list(r.generated) for r in rep.done},
        "logits": {k: np.stack(v) for k, v in eng.logit_trace.items()},
        "switch_log": rep.switches,
        "peak_bytes": peak,
    }
    del eng  # free weights and cache before the reference
    gc.collect()
    pad_to = ceil_to(prompt_lens[1] + max_new, 128)
    params = reference_params(cfg, devices[0], seed, econf.dtype)
    out["max_dev"] = teacher_forced_deviation(
        cfg, devices[0], params, rep.done, out["logits"], pad_to
    )
    print(f"teacher-forced max logit deviation {out['max_dev']:.6f} (tolerance {LOGIT_TOL})")
    if stats:
        print(f"device 0 peak_bytes_in_use after the reference: "
              f"{devices[0].memory_stats()['peak_bytes_in_use']}")
    check(out["max_dev"] < LOGIT_TOL, f"max logit deviation {out['max_dev']}")
    return out


def compare_runs(a, b):
    """Max deviation between two runs' logits over each request's common
    token prefix (greedy paths may part where two logits nearly tie)."""
    worst, same = 0.0, 0
    for rid, ga in a["generated"].items():
        gb = b["generated"][rid]
        n = next((i for i, (x, y) in enumerate(zip(ga, gb)) if x != y), len(ga))
        n = min(n + 1, len(ga), len(gb))  # the first differing token's logits count too
        same += ga == gb
        dev = check_rel_dev(a["logits"][rid][:n], b["logits"][rid][:n], f"request {rid}")
        worst = max(worst, dev)
    return worst, same


def run_phases(cfg, devices, econf, **kw):
    """One device: serve at TP 1 and check. Four: serve with TP switching
    1->2->4->1 mid-decode, then at fixed TP 1 on the same devices; check both
    against the reference and against each other."""
    if len(devices) == 1:
        serve_and_check(cfg, devices, replace(econf, candidate_tps=(1,)), **kw)
        return
    print("-- TP 1->2->4->1 mid-decode")
    switched = serve_and_check(cfg, devices, replace(econf, candidate_tps=(1, 2, 4)), **kw)
    path = [(s["from_tp"], s["to_tp"]) for s in switched["switch_log"]]
    check(path == [(1, 2), (2, 4), (4, 1)], f"switch path {path}")
    print("-- fixed TP 1")
    fixed = serve_and_check(cfg, devices, replace(econf, candidate_tps=(1,)), **kw)
    dev, same = compare_runs(switched, fixed)
    print(f"switched vs fixed TP 1: {same}/{len(fixed['generated'])} identical "
          f"token paths, max logit deviation {dev:.6f} (tolerance {LOGIT_TOL})")
    check(dev < LOGIT_TOL, f"switched vs fixed deviation {dev}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    devices = devices[: args.chips]

    from repro.configs import get_config
    from repro.launch.serve import enable_compile_cache
    from repro.serving.engine import EngineConfig

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("h2o-danube-1.8b")
    print(f"{cfg.name}: {cfg.param_count()} params, {cfg.param_count() * 2} weight bytes (bf16)")
    print(f"device_kind {devices[0].device_kind}, device count {len(devices)}")
    run_phases(cfg, devices, EngineConfig())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
