"""The open-loop driver. It owns the clock and the queue of due requests,
and calls only the engine's public entries, in `ServingEngine.run`'s order
with arrival times added. Each iteration:

  1. if the mix's schedule names another TP for this moment, `switch_tp`;
  2. offer due requests in FIFO order to `admit` until it returns False;
  3. `step()` once, if anything is in flight.

It never reads the engine's slot state and does not assume that `admit`
produces the first token: after every call it looks at each in-flight
request's generated tokens and stamps the new ones with the time the call
returned. Every call runs inside a span of the same name ("switch",
"admit", "step"), which a traced run sees in the profiler's trace.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

from stats import Record


@dataclass
class Calls:
    """What the driver did, by call, on its own clock (seconds from the
    window's start): the counters and spans the per-layer readers use."""
    admits: List[tuple] = field(default_factory=list)  # (start, end, prompt_len)
    refused: int = 0
    steps: List[tuple] = field(default_factory=list)  # (start, end, tp, contexts)
    switches: List[tuple] = field(default_factory=list)  # (start, end, from, to)


class Driver:
    def __init__(self, engine, requests, make_request, tp_at: Callable[[float], Optional[int]],
                 clock=time.perf_counter):
        self.eng = engine
        self.reqs = sorted(requests, key=lambda r: (r.due_s, r.req_id))
        self.make_request = make_request
        self.tp_at = tp_at
        self.clock = clock
        self.records: Dict[int, Record] = {
            r.req_id: Record(r.req_id, r.tier, r.due_s, len(r.prompt), r.max_new)
            for r in self.reqs
        }
        self.engine_reqs: Dict[int, object] = {}
        self.inflight: Dict[int, object] = {}
        self.queue: deque = deque()
        self.finished: List = []
        self.calls = Calls()
        self.tp = getattr(engine, "tp", None)
        self.t0 = None
        self.seconds = 0.0
        self._next = 0

    def now(self) -> float:
        return self.clock() - self.t0

    def _stamp(self, t: float) -> None:
        for rid, er in self.inflight.items():
            rec = self.records[rid]
            n = len(er.generated)
            if n > rec.tokens:
                if rec.first_s is None:
                    rec.first_s = t
                rec.last_s = t
                if t <= self.seconds:
                    rec.tokens_in_window += n - rec.tokens
                rec.tokens = n

    def _release(self, t: float) -> None:
        while self._next < len(self.reqs) and self.reqs[self._next].due_s <= t:
            self.queue.append(self.reqs[self._next])
            self._next += 1

    def _offer(self) -> None:
        while self.queue:
            r = self.queue[0]
            er = self.engine_reqs.get(r.req_id) or self.make_request(r)
            self.engine_reqs[r.req_id] = er
            start = self.now()
            with jax.profiler.TraceAnnotation("admit"):
                ok = self.eng.admit(er, start)
            end = self.now()
            if not ok:
                self.calls.refused += 1
                break
            self.queue.popleft()
            self.inflight[r.req_id] = er
            self.calls.admits.append((start, end, len(r.prompt)))
            self._stamp(end)

    def _step(self) -> None:
        ctx = [len(er.prompt) + len(er.generated) for er in self.inflight.values()]
        start = self.now()
        with jax.profiler.TraceAnnotation("step"):
            done = self.eng.step()
        end = self.now()
        self.calls.steps.append((start, end, self.tp, ctx))
        self._stamp(end)
        for er in done:
            self.inflight.pop(er.req_id, None)
            self.records[er.req_id].finished = True
            self.finished.append(er)

    def _switch(self, tp: int) -> None:
        start = self.now()
        with jax.profiler.TraceAnnotation("switch"):
            self.eng.switch_tp(tp)
        self.calls.switches.append((start, self.now(), self.tp, tp))
        self.tp = tp

    def iterate(self) -> None:
        t = self.now()
        tp = self.tp_at(t)
        if tp is not None and tp != self.tp:
            self._switch(tp)
        self._release(t)
        self._offer()
        if self.inflight:
            self._step()

    def fill(self) -> None:
        """Before the window (a batch cell): offer every request due at 0
        until the engine refuses one, and step until each admitted request
        holds a token."""
        self.t0 = self.clock()
        self._release(0.0)
        self._offer()
        while any(len(er.generated) == 0 for er in self.inflight.values()):
            self._step()

    def run_window(self, seconds: float) -> None:
        """Serve for `seconds` from now; t=0 of every request is this moment."""
        self.seconds = seconds
        shift = self.clock() - self.t0 if self.t0 is not None else 0.0
        self.t0 = self.clock()
        for rec in self.records.values():  # stamps of a pre-window fill
            if rec.first_s is not None:
                rec.first_s -= shift
                rec.last_s -= shift
        with jax.profiler.TraceAnnotation("window"):
            while self.now() < seconds:
                if not self.inflight and not self.queue:
                    if self._next >= len(self.reqs):
                        break
                    wait = min(self.reqs[self._next].due_s, seconds) - self.now()
                    if wait > 0:
                        time.sleep(wait)
                        continue
                self.iterate()

    def drain(self, cap_s: float) -> float:
        """Keep serving the requests due in the window until each has
        finished or `cap_s` has passed; returns the time it stopped."""
        while (self.inflight or self.queue or self._next < len(self.reqs)) \
                and self.now() < self.seconds + cap_s:
            if not self.inflight and not self.queue:
                time.sleep(max(0.0, min(self.reqs[self._next].due_s - self.now(), 0.05)))
            self.iterate()
        return self.now()
