"""The traffic generator: deterministic by seed, the same work for every
seed in another order, and the stated means and clips."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import traffic

HERE = Path(__file__).resolve().parent
# an open mix of two tiers whose shares drift by phase, with ServeGen's
# conversation and code lengths, switching TP at each phase
DRIFT = {
    "arrivals": "open", "rate_rps": 6.0, "phase_s": 10, "drain_s": 60,
    "classes": {
        "conversation": {"prompt": {"mean": 871, "sigma": 0.9, "lo": 16, "hi": 1536},
                         "output": {"mean": 86, "sigma": 0.7, "lo": 2}},
        "code": {"prompt": {"mean": 912, "sigma": 0.9, "lo": 16, "hi": 1536},
                 "output": {"mean": 148, "sigma": 0.7, "lo": 2}},
    },
    "phases": [
        {"name": "interactive", "tp": 4,
         "mix": [{"class": "conversation", "tier": "strict", "share": 0.8},
                 {"class": "code", "tier": "relaxed", "share": 0.2}]},
        {"name": "background", "tp": 1,
         "mix": [{"class": "conversation", "tier": "strict", "share": 0.2},
                 {"class": "code", "tier": "relaxed", "share": 0.8}]},
    ],
}
BATCH = json.loads((HERE / "traffic" / "decode-batch.json").read_text())


def _key(reqs):
    return [(r.tier, r.cls, r.due_s, len(r.prompt), r.max_new, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("mix", [DRIFT, BATCH], ids=["tier-drift", "decode-batch"])
def test_same_seed_same_inputs(mix):
    a = traffic.generate(mix, 45, 2**40 + 17, 32000, 2048)
    b = traffic.generate(mix, 45, 2**40 + 17, 32000, 2048)
    assert _key(a) == _key(b)
    c = traffic.generate(mix, 45, 2**40 + 18, 32000, 2048)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [DRIFT, BATCH], ids=["tier-drift", "decode-batch"])
def test_every_seed_gets_the_same_work(mix):
    runs = [traffic.generate(mix, 45, s, 32000, 2048) for s in (1, 2, 3**20)]
    shapes = [Counter((r.tier, r.cls, len(r.prompt), r.max_new) for r in rs) for rs in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    if mix["arrivals"] == "open":
        assert len(runs[0]) == round(mix["rate_rps"] * 45)
        assert [r.due_s for r in runs[0]] != [r.due_s for r in runs[1]]


def test_lognormal_quantiles_reproduce_the_stated_mean():
    for mean, sigma in ((871, 0.9), (86, 0.7), (912, 0.9), (148, 0.7), (256, 0.9), (768, 0.7)):
        x = traffic.lognormal_quantiles(mean, sigma, 20000)
        assert abs(x.mean() / mean - 1) < 0.01
        assert abs(np.median(x) / (mean * np.exp(-sigma**2 / 2)) - 1) < 0.01


def test_exponential_gaps_span_the_phase():
    g = traffic.exponential_gaps(80, 10.0)
    assert abs(g.sum() - 10.0) < 1e-9 and (g > 0).all()
    assert abs(np.median(g) / (np.log(2) * 10 / 80) - 1) < 0.05


def test_clips_and_phase_mix():
    reqs = traffic.generate(DRIFT, 40, 5, 32000, 2048)
    for r in reqs:
        c = DRIFT["classes"][r.cls]
        assert c["prompt"]["lo"] <= len(r.prompt) <= c["prompt"]["hi"]
        assert c["output"]["lo"] <= r.max_new and len(r.prompt) + r.max_new <= 2048
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 32000
        assert (r.tier == "strict") == (r.cls == "conversation")
    inter = [r for r in reqs if traffic.phase_at(DRIFT, r.due_s)["name"] == "interactive"]
    back = [r for r in reqs if traffic.phase_at(DRIFT, r.due_s)["name"] == "background"]
    assert abs(sum(r.tier == "strict" for r in inter) / len(inter) - 0.8) < 0.03
    assert abs(sum(r.tier == "strict" for r in back) / len(back) - 0.2) < 0.03
    assert all(0 <= r.due_s < 40 for r in reqs)


def test_schedule():
    assert [traffic.tp_at(DRIFT, t) for t in (0, 9.9, 10, 25, 31, 45)] == [4, 4, 1, 4, 1, 4]
    assert traffic.tps(DRIFT) == [1, 4] and traffic.tps(BATCH) == [1]
    b = traffic.generate(BATCH, 45, 9, 64000, 2048)
    assert len(b) == BATCH["batch_requests"] and all(r.due_s == 0.0 for r in b)
