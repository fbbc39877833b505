"""Percentile and tiered-goodput arithmetic on synthetic records, and the
end-to-end metric files that use it."""
from types import SimpleNamespace

import pytest

import harness
import stats

TIERS = {"strict": {"ttft_ms": 500.0, "tpot_ms": 40.0},
         "relaxed": {"ttft_ms": 500.0, "tpot_ms": 100.0}}


def rec(i, tier, due, first, last, tokens, finished=True, in_window=None):
    return stats.Record(i, tier, due, 100, tokens, first, last, tokens,
                        tokens if in_window is None else in_window, finished)


def summary(records, seconds=10.0, end_s=20.0):
    return SimpleNamespace(records=records, seconds=seconds, end_s=end_s, setup_s=3.5,
                           tiers=TIERS, calls=None)


RECS = [
    rec(0, "strict", 0.0, 0.2, 0.2 + 9 * 0.030, 10),   # ttft 200, tpot 30: meets
    rec(1, "strict", 1.0, 1.6, 1.6 + 9 * 0.030, 10),   # ttft 600: misses
    rec(2, "strict", 2.0, 2.1, 2.1 + 9 * 0.050, 10),   # tpot 50: misses strict
    rec(3, "relaxed", 3.0, 3.1, 3.1 + 9 * 0.050, 10),  # tpot 50: meets relaxed
    rec(4, "relaxed", 4.0, 4.3, 4.3 + 0.2, 5, finished=False),  # unfinished: misses
    rec(5, "relaxed", 9.0, None, None, 0, finished=False),      # never started
]


def test_goodput_counts_only_finished_requests_within_their_tier():
    tiers = {n: stats.Tier(n, **t) for n, t in TIERS.items()}
    assert [stats.meets(r, tiers[r.tier]) for r in RECS] == [True, False, False, True, False, False]
    assert stats.goodput_rps(RECS, tiers, 10.0) == pytest.approx(0.2)
    assert harness.module("e2e", "goodput_rps").read(summary(RECS)) == pytest.approx(0.2)


def test_ttft_is_from_the_due_time_and_censored_where_no_token_came():
    assert RECS[1].ttft_ms(99) == pytest.approx(600)
    assert RECS[5].ttft_ms(20.0) == pytest.approx(11000)  # end_s - due
    ttfts = [200, 600, 100, 100, 300, 11000]
    got = harness.module("e2e", "ttft_p90_ms").read(summary(RECS))
    assert got == pytest.approx(stats.percentile(ttfts, 90))
    # numpy's linear rule: rank 0.9 * 5 = 4.5 between 600 and 11000
    assert got == pytest.approx(600 + 0.5 * (11000 - 600))


def test_tpot_over_requests_with_two_tokens_or_more():
    assert RECS[0].tpot_ms == pytest.approx(30)
    assert RECS[4].tpot_ms == pytest.approx(50)  # 0.2 s over 4 gaps, unfinished
    assert RECS[5].tpot_ms is None
    got = harness.module("e2e", "tpot_p90_ms").read(summary(RECS))
    assert got == pytest.approx(stats.percentile([30, 30, 50, 50, 50], 90))


def test_tokens_per_s_counts_tokens_inside_the_window():
    recs = [rec(0, "relaxed", 0, 0.1, 5, 100, in_window=80), rec(1, "relaxed", 0, 1, 3, 20)]
    assert harness.module("e2e", "tokens_per_s").read(summary(recs, seconds=4.0)) == 25.0
    assert harness.module("e2e", "setup_s").read(summary(recs)) == 3.5


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None
