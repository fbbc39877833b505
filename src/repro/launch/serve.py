"""Serving launcher: a registered model config, at its published widths,
served by the adaptive-TP engine on the devices JAX finds.

    PYTHONPATH=src python -m repro.launch.serve --config h2o-danube-1.8b \
        [--chips 4] [--switch-every 8] [--requests 24] [--max-new 32] [--seed 0]

Weights are random, drawn from --seed, in bf16. The engine serves a seeded
batch of two-tier (strict / relaxed) requests through warmup / admit / step
with continuous batching over 16 slots of 2048 tokens. With more than one
chip it switches TP through every candidate level and back, one switch each
--switch-every decode steps. Latencies are host-clock times; every fetch of
a generated token waits for the device.

CPU rehearsals give JAX virtual devices from outside, e.g.
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models.model import model_param_defs
from repro.models.params import init_params
from repro.parallel.sharding import make_exec_config
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.request import Request

# fixed, so that one checkout's runs find each other's compiled programs
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX keeps the cache there by
    itself and no other directory is set; otherwise the cache goes to
    CACHE_DIR inside the checkout. Returns the directory in use.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def make_requests(
    cfg: ModelConfig, n: int, max_new: int, seed: int,
    prompt_lens: Tuple[int, int] = (32, 480),
) -> List[Request]:
    """`n` seeded requests alternating strict / relaxed, prompt lengths drawn
    uniformly from `prompt_lens` (inclusive), token ids from the vocab."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        reqs.append(Request(i, ("strict", "relaxed")[i % 2], prompt, max_new))
    return reqs


def build_engine(
    cfg: ModelConfig, devices: Sequence, econf: EngineConfig, seed: int
) -> ServingEngine:
    """Engine over `devices` with random weights from `seed` in econf.dtype.
    The canonical weights are dropped once the weight store has placed them."""
    defs = model_param_defs(cfg, make_exec_config(cfg, 1))
    params = init_params(defs, jax.random.PRNGKey(seed), econf.dtype)
    return ServingEngine(cfg, params, devices, econf)


def switch_schedule(tps: Sequence[int], every: int) -> Dict[int, int]:
    """{decode step: tp} visiting tps[1:] in order and then tps[0] again, one
    switch every `every` steps; empty for a single TP."""
    if len(tps) < 2 or every <= 0:
        return {}
    order = list(tps[1:]) + [tps[0]]
    return {every * (i + 1): tp for i, tp in enumerate(order)}


@dataclass
class ServeReport:
    done: List[Request]
    wall_s: float
    ttft_s: np.ndarray  # per request, from the batch's arrival
    tpot_s: np.ndarray  # per request with more than one token
    switches: List[dict]  # the engine's switch_log entries of this run

    @property
    def tokens(self) -> int:
        return sum(len(r.generated) for r in self.done)

    def lines(self) -> List[str]:
        def p50_max(x):
            return f"p50 {np.median(x) * 1e3:.3f} ms, max {np.max(x) * 1e3:.3f} ms"

        return [
            f"served {len(self.done)} requests, {self.tokens} tokens generated "
            f"in {self.wall_s:.3f} s: {self.tokens / self.wall_s:.1f} tokens/s",
            f"TTFT {p50_max(self.ttft_s)} (host clock, queueing included)",
            f"TPOT {p50_max(self.tpot_s)} (host clock)",
        ] + [
            f"switch TP {sw['from_tp']}->{sw['to_tp']} at step {sw['step']}: "
            f"rebind_s {sw['rebind_s']:.6f} migrate_s {sw['migrate_s']:.6f}"
            for sw in self.switches
        ]


def serve(
    eng: ServingEngine, requests: List[Request], schedule: Dict[int, int]
) -> ServeReport:
    """Serve `requests`, all arriving now, to completion."""
    n_switches = len(eng.stats.switch_log)
    t0 = time.perf_counter()
    for r in requests:
        r.arrival_s = t0
    done = eng.run(requests, switch_schedule=schedule)
    wall = time.perf_counter() - t0
    if len(done) != len(requests):
        raise RuntimeError(f"served {len(done)} of {len(requests)} requests")
    ttft = np.array([r.first_token_s - r.arrival_s for r in done])
    tpot = np.array([
        (r.finish_s - r.first_token_s) / (len(r.generated) - 1)
        for r in done if len(r.generated) > 1
    ])
    return ServeReport(done, wall, ttft, tpot, eng.stats.switch_log[n_switches:])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="h2o-danube-1.8b")
    ap.add_argument("--chips", type=int, default=1, help="devices to serve on")
    ap.add_argument("--switch-every", type=int, default=8,
                    help="decode steps between TP switches (0: no switches)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()[: args.chips]
    if len(devices) < args.chips:
        raise SystemExit(f"asked for {args.chips} chips, JAX has {len(devices)}")
    cfg = get_config(args.config)
    eng = build_engine(cfg, devices, EngineConfig(), args.seed)
    print(f"{cfg.name}: {cfg.param_count() / 1e9:.3f}B params on "
          f"{len(devices)} x {devices[0].device_kind}, TPs {eng.tps}")
    print(f"warmup (compile) {eng.warmup():.1f} s")
    reqs = make_requests(cfg, args.requests, args.max_new, args.seed)
    rep = serve(eng, reqs, switch_schedule(eng.tps, args.switch_every))
    for line in rep.lines():
        print(line)
    print(f"decode steps {eng.stats.steps}; final TP {eng.tp}")


if __name__ == "__main__":
    main()
