"""The driver against a stub engine: FIFO admission until refused, one step
per iteration, switches from the mix's schedule, and tokens stamped when the
call that produced them returns, wherever the engine produces them."""
from types import SimpleNamespace

import numpy as np
import pytest

import driver
import traffic


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001  # every look at the clock moves it 1 ms
        return self.t


class StubEngine:
    """Holds `slots` requests; it has no slot state for the driver to read.
    With first_in_admit=False the first token comes from the next step."""

    def __init__(self, slots, first_in_admit=True):
        self.slots_cap, self.first_in_admit = slots, first_in_admit
        self.active, self.log, self.tp = [], [], 1

    def admit(self, req, now):
        if len(self.active) >= self.slots_cap:
            self.log.append(("refused", req.req_id))
            return False
        self.log.append(("admit", req.req_id))
        self.active.append(req)
        if self.first_in_admit:
            req.generated.append(7)
        return True

    def step(self):
        self.log.append(("step",))
        done = []
        for r in list(self.active):
            r.generated.append(8)
            if len(r.generated) >= r.max_new_tokens:
                self.active.remove(r)
                done.append(r)
        return done

    def switch_tp(self, tp):
        self.log.append(("switch", tp))
        self.tp = tp


def reqs(dues, max_new=3):
    return [traffic.Req(i, "strict", "c", d, np.arange(5, dtype=np.int32), max_new)
            for i, d in enumerate(dues)]


def make(eng, rs, tp_at=lambda t: None):
    mk = lambda r: SimpleNamespace(req_id=r.req_id, prompt=r.prompt, generated=[],  # noqa: E731
                                   max_new_tokens=r.max_new)
    d = driver.Driver(eng, rs, mk, tp_at, clock=Clock())
    d.t0, d.seconds = d.clock(), 10.0
    return d


def test_fifo_admission_until_refused_then_one_step():
    eng = StubEngine(slots=2)
    d = make(eng, reqs([0, 0, 0, 0]))
    d.iterate()
    assert eng.log == [("admit", 0), ("admit", 1), ("refused", 2), ("step",)]
    assert list(q.req_id for q in d.queue) == [2, 3]
    eng.log.clear()
    d.iterate()  # nothing finished yet: 2 is offered again first, and refused
    assert eng.log == [("refused", 2), ("step",)]
    eng.log.clear()
    d.iterate()  # 0 and 1 finished in the last step
    assert eng.log == [("admit", 2), ("admit", 3), ("step",)]
    assert [r.req_id for r in d.finished] == [0, 1]
    assert all(d.records[i].finished for i in (0, 1))


def test_requests_are_offered_only_once_due():
    eng = StubEngine(slots=4)
    d = make(eng, reqs([0.0, 5.0]))
    d.iterate()
    assert ("admit", 1) not in eng.log
    d.clock.t += 5.0
    d.iterate()
    assert ("admit", 1) in eng.log


@pytest.mark.parametrize("first_in_admit", [True, False])
def test_tokens_are_stamped_when_the_producing_call_returns(first_in_admit):
    eng = StubEngine(slots=1, first_in_admit=first_in_admit)
    d = make(eng, reqs([0.0], max_new=3))
    d.iterate()
    rec = d.records[0]
    admit_end = d.calls.admits[0][1]
    step_end = d.calls.steps[0][1]
    assert rec.first_s == pytest.approx(admit_end if first_in_admit else step_end)
    while not rec.finished:
        d.iterate()
    assert rec.tokens == 3 and rec.last_s == pytest.approx(d.calls.steps[-1][1])
    assert d.calls.steps[0][3] == [5 + (1 if first_in_admit else 0)]  # live context


def test_switches_follow_the_schedule():
    eng = StubEngine(slots=4)
    d = make(eng, reqs([0.0]), tp_at=lambda t: 4 if t < 0.01 else 1)
    d.tp = 1
    d.iterate()
    assert eng.log[0] == ("switch", 4)
    d.clock.t += 1
    d.iterate()
    assert ("switch", 1) in eng.log
    assert [(a, b) for _, _, a, b in d.calls.switches] == [(1, 4), (4, 1)]


def test_window_and_drain_on_the_wall_clock():
    eng = StubEngine(slots=2)
    rs = reqs([0.0, 0.05, 0.1, 0.15], max_new=4)
    d = driver.Driver(eng, rs, lambda r: SimpleNamespace(
        req_id=r.req_id, prompt=r.prompt, generated=[], max_new_tokens=r.max_new),
        lambda t: None)
    d.run_window(0.2)
    end = d.drain(5.0)
    assert all(rec.finished for rec in d.records.values())
    assert end < 5.2
    assert sum(rec.tokens for rec in d.records.values()) == 16
