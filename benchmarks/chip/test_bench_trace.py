"""The reduction from trace to per-layer numbers, on hand-made intervals and
on small recorded traces under `testdata/`: `xla_cpu_trace.json.gz` is 150
ms of a tiny open-loop run on the CPU backend, its XLA:CPU executor's op
events standing in for a device's ops (no chip trace could be recorded when
it was made)."""
from pathlib import Path

import numpy as np
import pytest

import tracereduce

TRACES = [Path(__file__).resolve().parent / "testdata" / "xla_cpu_trace.json.gz"]


def test_union_and_overlap_by_hand():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracereduce.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    ev = {"devices": {0: [[10, 20, "p/a"], [15, 30, "p/b"], [50, 60, "q/c"]],
                      1: [[0, 100, "p/a"]]},
          "host": [["window", 0, 100], ["step", 10, 40], ["admit", 45, 70]]}
    r = tracereduce.Reduced(ev)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((30 + 100) / 2 * 1e-9)
    assert r.idle_share == pytest.approx(1 - 0.65)
    assert r.device_in("step") == pytest.approx((20 + 30) / 2 * 1e-9)
    assert r.device_in("admit") == pytest.approx((10 + 25) / 2 * 1e-9)
    assert r.device_in("switch") == 0
    # device 0 idles over 0..10 (no span), 30..50 (step 10, wait 5, admit 5)
    # and 60..100 (admit 10, wait 30)
    assert r.idle_gaps() == [["wait", pytest.approx(40e-9)], ["step", pytest.approx(20e-9)],
                             ["wait", pytest.approx(10e-9)]]
    tops = dict(r.top_ops())
    assert tops["p/a"] == pytest.approx((10 + 100) / 2 * 1e-9)


def _mask(intervals, lo, hi, res):
    m = np.zeros(int((hi - lo) / res) + 1, bool)
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            m[int((a - lo) / res):int((b - lo) / res)] = True
    return m


@pytest.mark.parametrize("trace", TRACES, ids=lambda p: p.name)
def test_recorded_trace_against_a_sampled_count(trace):
    ev = tracereduce.read(str(trace))
    r = tracereduce.Reduced(ev)
    lo, hi = r.win
    res = 100.0  # ns
    busy = [_mask([(s, e) for s, e, _ in ops], lo, hi, res) for ops in ev["devices"].values()]
    assert r.idle_share == pytest.approx(1 - np.mean([b.mean() for b in busy]), abs=2e-3)
    assert 0 < r.idle_share < 1
    for span in ("step", "admit"):
        sp = _mask([(s, e) for n, s, e in ev["host"] if n == span], lo, hi, res)
        want = np.mean([(b & sp).sum() for b in busy]) * res * 1e-9
        assert r.device_in(span) == pytest.approx(want, rel=5e-3, abs=2e-6)
    assert r.device_in("step") > 0
    for label, secs in r.idle_gaps():
        assert label in ("admit", "step", "switch", "wait") and secs > 0
    assert sum(v for _, v in r.top_ops(10**6)) == pytest.approx(
        sum(sum(e - s for s, e, _ in ops if s >= lo and e <= hi) for ops in ev["devices"].values())
        * 1e-9 / r.n_devices, rel=0.05)
