"""Client-side records and the arithmetic over them: percentiles and tiered
goodput. The tier rule is the one of the program's `core/goodput.py`
(`GoodputMeter.meets_slo`), kept here so that no change to the program can
change how it is judged.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class Tier:
    name: str
    ttft_ms: float
    tpot_ms: float


@dataclass
class Record:
    """One request as its client saw it, on the driver's clock (seconds from
    the window's start)."""
    req_id: int
    tier: str
    due_s: float
    prompt_len: int
    max_new: int
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    tokens: int = 0
    tokens_in_window: int = 0
    finished: bool = False

    def ttft_ms(self, end_s: float) -> float:
        """From the due time; a request that never got a token is censored
        at `end_s`, the moment the run stopped waiting for it."""
        t = self.first_s if self.first_s is not None else end_s
        return (t - self.due_s) * 1e3

    @property
    def tpot_ms(self) -> Optional[float]:
        if self.tokens < 2:
            return None
        return (self.last_s - self.first_s) * 1e3 / (self.tokens - 1)


def meets(rec: Record, tier: Tier) -> bool:
    """Finished, and within both limits of its tier; a request that failed or
    did not finish misses."""
    if not rec.finished or rec.first_s is None:
        return False
    tpot = rec.tpot_ms if rec.tpot_ms is not None else 0.0
    return rec.ttft_ms(rec.first_s) <= tier.ttft_ms and tpot <= tier.tpot_ms


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def goodput_rps(records: List[Record], tiers: Dict[str, Tier], seconds: float) -> float:
    return sum(meets(r, tiers[r.tier]) for r in records) / seconds
