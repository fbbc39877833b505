"""The one traffic generator. A mix is a JSON file of parameters under
`traffic/`; this module turns it, a window length and a seed into requests.

Every seed gets the same work in another order: lengths are the quantiles of
their lognormal distribution and inter-arrival gaps the quantiles of an
exponential one, shuffled by the seed, and each phase holds a fixed number
of requests of each class. So runs with different seeds differ by order and
token ids, not by how much there is to do.

Mix keys:
  classes   {name: {"prompt": {mean, sigma, lo, hi}, "output": {mean, sigma, lo}}}
            lognormal lengths with the given mean, clipped to [lo, hi];
            an output is also clipped so that prompt + output <= max_len.
  arrivals  "open": Poisson at `rate_rps` in total, due times from the
            window's start; "batch": `batch_requests` requests, all due
            when the window opens, served as slots free.
  phases    [{"tp": t, "mix": [{"class", "tier", "share"}]}], each lasting
            `phase_s` and repeated in turn; `tp` is the tensor-parallel
            degree the driver switches to when the phase starts.
  tiers     {name: {"ttft_ms", "tpot_ms"}}: the limits goodput counts against.
  drain_s   how long after the window requests due in it may still finish.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_N01 = NormalDist()


@dataclass
class Req:
    req_id: int
    tier: str
    cls: str
    due_s: float
    prompt: np.ndarray
    max_new: int


def lognormal_quantiles(mean: float, sigma: float, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a lognormal of the given mean."""
    mu = math.log(mean) - sigma**2 / 2
    qs = [(i + 0.5) / n for i in range(n)]
    return np.array([math.exp(mu + sigma * _N01.inv_cdf(q)) for q in qs])


def exponential_gaps(n: int, span_s: float) -> np.ndarray:
    """n inter-arrival gaps at the mid-quantiles of an exponential, scaled so
    that they add up to `span_s`."""
    g = np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)])
    return g * (span_s / g.sum())


def phase_at(mix: dict, t: float) -> dict:
    """The phase in force at `t` seconds from the window's start."""
    phases = mix["phases"]
    return phases[int(max(t, 0.0) // mix.get("phase_s", math.inf)) % len(phases)]


def tp_at(mix: dict, t: float) -> Optional[int]:
    return phase_at(mix, t).get("tp")


def tps(mix: dict) -> List[int]:
    return sorted({p.get("tp", 1) for p in mix["phases"]})


def _slots(mix: dict, seconds: float):
    """[(phase start, phase length, n requests, phase)] covering the window."""
    if mix["arrivals"] == "batch":
        return [(0.0, 0.0, int(mix["batch_requests"]), mix["phases"][0])]
    out, t, k = [], 0.0, 0
    step = mix.get("phase_s", seconds)
    while t < seconds - 1e-9:
        dur = min(step, seconds - t)
        n = int(round(mix["rate_rps"] * dur))
        out.append((t, dur, n, mix["phases"][k % len(mix["phases"])]))
        t, k = t + dur, k + 1
    return out


def generate(mix: dict, seconds: float, seed: int, vocab: int, max_len: int) -> List[Req]:
    rng = np.random.default_rng(seed)
    slots = _slots(mix, seconds)
    # (phase index, class, tier) for every request, in phase order
    plan = []
    for k, (_, _, n, ph) in enumerate(slots):
        counts = [int(round(m["share"] * n)) for m in ph["mix"]]
        counts[-1] = n - sum(counts[:-1])
        tags = [(m["class"], m["tier"]) for m, c in zip(ph["mix"], counts) for _ in range(c)]
        rng.shuffle(tags)
        plan += [(k, c, t) for c, t in tags]
    # a shuffled pool of quantile lengths per class
    pools = {}
    for cls in {c for _, c, _ in plan}:
        n = sum(1 for _, c, _ in plan if c == cls)
        spec = mix["classes"][cls]
        p = spec["prompt"]
        o = spec["output"]
        pl = np.clip(lognormal_quantiles(p["mean"], p["sigma"], n).astype(int), p["lo"], p["hi"])
        ol = np.clip(lognormal_quantiles(o["mean"], o["sigma"], n).astype(int), o["lo"], None)
        # prompt and output lengths paired by a fixed draw, the pairs dealt by the seed
        ol = ol[np.random.default_rng(n).permutation(n)]
        order = rng.permutation(n)
        pools[cls] = [list(pl[order]), list(ol[order])]
    reqs = []
    for k, (start, dur, n, _) in enumerate(slots):
        gaps = rng.permutation(exponential_gaps(n, dur)) if dur and n else np.zeros(n)
        due = start + np.cumsum(gaps) - gaps  # the first is due as its phase starts
        mine = [(c, t) for kk, c, t in plan if kk == k]
        for (cls, tier), d in zip(mine, due):
            plen = int(pools[cls][0].pop())
            out = int(min(pools[cls][1].pop(), max_len - plen))
            prompt = rng.integers(0, vocab, plen, dtype=np.int32)
            reqs.append(Req(len(reqs), tier, cls, float(d), prompt, max(out, 1)))
    return reqs
