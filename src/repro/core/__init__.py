"""Nitsum core: adaptive tensor parallelism as a runtime control surface.

  weight_store — storage-TP weight layout whose per-device bytes are
      identical at every execution TP level (zero-copy TP switching).
  migration    — KV/state re-partitioning plans and collective programs.
  planner      — goodput-efficiency estimation + weighted greedy GPU
      assignment (paper §3.3.1).
  goodput      — SLO tiers and TTFT/TPOT goodput accounting.
"""
from repro.core.goodput import SLOTier, GoodputMeter
from repro.core.planner import CandidateConfig, Planner, PlannerInputs
from repro.core.weight_store import WeightStore

__all__ = [
    "SLOTier",
    "GoodputMeter",
    "CandidateConfig",
    "Planner",
    "PlannerInputs",
    "WeightStore",
]
