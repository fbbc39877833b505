"""Shared benchmark setup: the paper's evaluation frame mapped to v5e.

The paper evaluates Llama-8B / Qwen-14B on 4–8 A100/H100s. A v5e chip has
~2.5–3x less HBM bandwidth than an A100, so the equivalent pool is 16 chips
for the 8B model (EXPERIMENTS.md §Setup notes the mapping); SLOs are derived
with the paper's SplitWise-style methodology (strict = bs-1 latency,
relaxed = bs-128) against the same analytic profile the planner uses.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Callable, List

from repro.configs import get_config
from repro.profiles.perf_model import PerfModel
from repro.profiles.slo import derive_tiers

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

MODEL = "llama3-8b"
N_CHIPS = 16
CANDIDATE_TPS = (1, 2, 4, 8)


def perf_model(arch: str = MODEL) -> PerfModel:
    return PerfModel(get_config(arch))


def tiers(perf: PerfModel = None):
    perf = perf or perf_model()
    return derive_tiers(perf, prompt_len=900, ctx_len=1000,
                        candidate_tps=CANDIDATE_TPS)


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def save_json(name: str, payload) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


def timed(fn: Callable) -> tuple:
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6
