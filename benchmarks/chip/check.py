"""What decides `correct`: the served tokens of a seeded sample of finished
requests, judged by the float32 reference.

For each sampled request the reference runs once over its prompt and served
tokens. At every served position it gives the gap by which the served
token's logit lies below the reference's best logit there; greedy decoding
at the configuration's precision keeps that gap near zero, and only ties
that rounding breaks the other way leave one. The number compared is the
widest gap over all sampled tokens. A non-finite logit makes it infinite.

The sample always holds the longest finished request and, where the window
switched TP, requests whose decoding spans each switch (so their cache went
through the migration); the rest are drawn from the seed until the sample
holds at least `min_seqs` requests and `min_tokens` served tokens, so that
it reaches several slots of the batch however long the longest one is.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

# The limit on the widest gap, in logits, where a configuration's `check`
# block gives no `gap_limit` of its own. Each lies between the largest reading
# of sound bf16 runs of the engine and the smallest of the float8 control, at
# the configuration's widths; PERF.md gives the readings each was set from.
DEFAULT_GAP_LIMIT = 0.06


def gap_limit(cfg: dict) -> float:
    return float(cfg["check"].get("gap_limit", DEFAULT_GAP_LIMIT))


def sample(finished: Sequence, records: Dict[int, object], switch_s: Sequence[float],
           seed: int, min_tokens: int, min_seqs: int) -> List:
    """Requests to check, drawn from `finished` (engine requests)."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    by_len = sorted(finished, key=lambda r: (-(r.prompt_len + len(r.generated)), r.req_id))
    chosen = [by_len[0]]
    for t in switch_s:
        span = [r for r in finished if records[r.req_id].first_s < t < records[r.req_id].last_s
                and r not in chosen]
        if span:
            chosen.append(span[int(rng.integers(len(span)))])
    rest = [r for r in finished if r not in chosen]
    for i in rng.permutation(len(rest)):
        if len(chosen) >= min_seqs and sum(len(r.generated) for r in chosen) >= min_tokens:
            break
        chosen.append(rest[i])
    return chosen


def widest_gap(seed, arch, cfg, reqs, device, precision="f32", against=None):
    """Widest gap of the served tokens below the reference's best, by the
    logits of the configuration's module `arch`.

    With `against` (the float32 reference's logits for the same rows), the
    reference itself is computed at `precision` and its own top token is
    judged instead: that is the control's reading.
    Returns (gap, tokens compared, logits at `precision`).
    """
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)]) for r in reqs]
    rows = [np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.generated)) for r in reqs]
    lg = arch.logits(seed, cfg, seqs, rows, device, precision)
    worst, n = 0.0, 0
    for i, r in enumerate(reqs):
        ref = lg[i] if against is None else against[i]
        if not (np.isfinite(lg[i]).all() and np.isfinite(ref).all()):
            return math.inf, n, lg
        tok = np.asarray(r.generated) if against is None else lg[i].argmax(-1)
        gap = ref.max(-1) - ref[np.arange(len(tok)), tok]
        worst = max(worst, float(gap.max()))
        n += len(tok)
    return worst, n, lg
