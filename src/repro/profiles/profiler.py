"""On-hardware profiler: measures the tables the planner consumes.

The paper expects admins to profile each accelerator type offline (§3.3.1).
`profile_decode`/`profile_prefill` time the real jitted step functions over
a (tp × batch × context) grid and emit the same table format as the
analytic model, so `TabulatedPerfModel` can drop into the Planner unchanged.
On this CPU container the measurements characterize the host (used in unit
tests for the machinery); on TPU the same code yields real v5e tables.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.profiles.perf_model import PerfModel


@dataclass
class ProfileTable:
    """Measured (tp, batch, ctx) -> seconds tables + interpolation."""

    decode_s: Dict[Tuple[int, int, int], float] = field(default_factory=dict)
    prefill_s: Dict[Tuple[int, int], float] = field(default_factory=dict)  # (tp, len)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(
                {
                    "decode": [[*k, v] for k, v in self.decode_s.items()],
                    "prefill": [[*k, v] for k, v in self.prefill_s.items()],
                },
                f,
            )

    @classmethod
    def load(cls, path: str) -> "ProfileTable":
        with open(path) as f:
            d = json.load(f)
        t = cls()
        for *k, v in d["decode"]:
            t.decode_s[tuple(k)] = v
        for *k, v in d["prefill"]:
            t.prefill_s[tuple(k)] = v
        return t

    def decode_time(self, batch: int, ctx: int, tp: int) -> float:
        keys = [k for k in self.decode_s if k[0] == tp]
        if not keys:
            raise KeyError(f"no decode profile for tp={tp}")
        # nearest-neighbor in log space + linear batch scaling beyond grid
        best = min(keys, key=lambda k: abs(np.log(k[1] / batch)) + abs(np.log(k[2] / max(ctx, 1))))
        base = self.decode_s[best]
        return base * max(batch / best[1], 1.0) ** 0.8

    def prefill_time(self, length: int, tp: int) -> float:
        keys = [k for k in self.prefill_s if k[0] == tp]
        if not keys:
            raise KeyError(f"no prefill profile for tp={tp}")
        best = min(keys, key=lambda k: abs(np.log(k[1] / max(length, 1))))
        return self.prefill_s[best] * length / best[1]


def time_fn(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def profile_engine(engine, batches: Sequence[int] = (1, 4), ctxs: Sequence[int] = (64,)) -> ProfileTable:
    """Profile a ServingEngine's decode executables over its TP levels."""
    import numpy as np

    table = ProfileTable()
    for tp in engine.tps:
        engine.switch_tp(tp)
        for b in batches:
            if b > engine.econf.n_slots:
                continue
            tokens = np.zeros((engine.econf.n_slots, 1), np.int32)
            pos = np.full((engine.econf.n_slots,), ctxs[0], np.int32)

            def step():
                nxt, _, engine.slots.arrays = engine._decode_fns[tp](
                    engine.storage, engine.slots.arrays, tokens, pos
                )
                return nxt

            dt = time_fn(step)
            for ctx in ctxs:
                table.decode_s[(tp, b, ctx)] = dt
        for L in engine.econf.prefill_buckets:
            toks = np.zeros((1, L), np.int32)
            dt = time_fn(lambda: engine._prefill_fns[tp](engine.storage, toks, L)[0])
            table.prefill_s[(tp, L)] = dt
    return table


class TabulatedPerfModel(PerfModel):
    """PerfModel backed by measured tables where available, analytic
    otherwise — the drop-in the Planner uses on real hardware."""

    def __init__(self, cfg, table: ProfileTable, **kw):
        super().__init__(cfg, **kw)
        object.__setattr__(self, "table", table)

    def decode_step_time_s(self, batch: int, ctx_len: int, tp: int) -> float:
        try:
            return self.table.decode_time(batch, ctx_len, tp)
        except KeyError:
            return super().decode_step_time_s(batch, ctx_len, tp)

    def prefill_time_s(self, prompt_len: int, tp: int, batch: int = 1) -> float:
        try:
            return self.table.prefill_time(prompt_len, tp) * batch
        except KeyError:
            return super().prefill_time_s(prompt_len, tp, batch)
