"""Planner + perf-model unit & property tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.goodput import GoodputMeter, RequestRecord, SLOTier
from repro.core.planner import Planner, PlannerInputs, TierDemand
from repro.profiles.perf_model import PerfModel


@pytest.fixture(scope="module")
def perf():
    return PerfModel(get_config("llama3-8b"))


def test_ttft_decreases_with_tp(perf):
    """Paper §2.2: higher TP reduces prefill latency (TTFT)."""
    ttfts = [perf.ttft_ms(2048, tp) for tp in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(ttfts, ttfts[1:])), ttfts


def test_decode_tp_crossover(perf):
    """Paper Fig. 2: per-chip-normalized decode throughput favors higher TP
    at small batch and lower TP at large batch."""
    def norm_tput(batch, tp):
        t = perf.decode_step_time_s(batch, 2048, tp)
        return batch / t / tp

    small = {tp: norm_tput(1, tp) for tp in (1, 2, 4, 8)}
    large = {tp: norm_tput(256, tp) for tp in (1, 2, 4, 8)}
    # at batch=1, TP>1 must not be catastrophically worse (within 2x) and the
    # TPOT itself must improve with TP:
    tpots = [perf.tpot_ms(1, 2048, tp) for tp in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(tpots, tpots[1:])), tpots
    # at batch=256 the normalized ranking flips toward low TP
    assert large[1] > large[8], large


def test_max_decode_batch_monotone_in_slo(perf):
    b_tight = perf.max_decode_batch(2048, 4, tpot_slo_ms=5.0)
    b_loose = perf.max_decode_batch(2048, 4, tpot_slo_ms=50.0)
    assert b_loose >= b_tight


def _planner(perf, tps=(1, 2, 4, 8)):
    tiers = [SLOTier("strict", 300.0, 10.0), SLOTier("relaxed", 300.0, 30.0)]
    return Planner(perf, tiers, candidate_tps=tps)


def test_plan_respects_budget_and_serves_demand(perf):
    pl = _planner(perf)
    inputs = PlannerInputs(
        demands={
            "strict": TierDemand(rps=5.0, prompt_len=1024, output_len=128),
            "relaxed": TierDemand(rps=20.0, prompt_len=2048, output_len=64),
        },
        total_chips=64,
    )
    plan = pl.plan(inputs)
    assert plan.chips_used() <= 64 + 1e-6
    assert set(plan.tiers) <= {"strict", "relaxed"}
    for name, tp in plan.tiers.items():
        assert tp.prefill.chips % tp.prefill.tp == 0
        assert tp.decode.chips % tp.decode.tp == 0
    assert plan.planning_ms < 1000.0


def test_weighted_greedy_fairness(perf):
    """A tier with large unmet demand must not be starved even when another
    tier is more chip-efficient (the paper's WGE weighting)."""
    pl = _planner(perf)
    inputs = PlannerInputs(
        demands={
            "strict": TierDemand(rps=50.0, prompt_len=4096, output_len=256),
            "relaxed": TierDemand(rps=50.0, prompt_len=256, output_len=16),
        },
        total_chips=32,
    )
    plan = pl.plan(inputs)
    assert "strict" in plan.tiers and plan.tiers["strict"].served_rps > 0


@settings(max_examples=20, deadline=None)
@given(
    rps1=st.floats(0.5, 50), rps2=st.floats(0.5, 50),
    chips=st.sampled_from([8, 16, 64, 128]),
    plen=st.sampled_from([256, 1024, 4096]),
)
def test_plan_budget_property(rps1, rps2, chips, plen):
    perf = PerfModel(get_config("llama3-8b"))
    pl = _planner(perf)
    inputs = PlannerInputs(
        demands={
            "strict": TierDemand(rps=rps1, prompt_len=plen, output_len=128),
            "relaxed": TierDemand(rps=rps2, prompt_len=plen, output_len=128),
        },
        total_chips=chips,
    )
    plan = pl.plan(inputs)
    assert plan.chips_used() <= chips + 1e-6
    for tp in plan.tiers.values():
        for stage in (tp.prefill, tp.decode):
            assert stage.chips >= 0
            assert stage.chips % stage.tp == 0


def test_goodput_meter():
    tiers = {"strict": SLOTier("strict", 100.0, 10.0)}
    m = GoodputMeter(tiers)
    m.add(RequestRecord(0, "strict", 0.0, 100, 10,
                        first_token_s=0.05, finish_s=0.11, tokens_out=10))
    m.add(RequestRecord(1, "strict", 0.0, 100, 10,
                        first_token_s=0.5, finish_s=0.6, tokens_out=10))  # TTFT miss
    assert m.goodput(horizon_s=1.0) == 1.0
    pct = m.latency_percentiles("strict")
    assert pct["ttft_ms_p50"] > 0
