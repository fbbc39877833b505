"""From a profiler trace to the numbers the per-layer readers use.

`load` reads the `.xplane.pb` that `jax.profiler` wrote into plain lists:
per device, the intervals in which an operation ran (the "XLA Ops" line,
each op labelled with the program it belongs to), and the host spans the
driver opened ("window", "admit", "step", "switch"). `Reduced` works on those
lists only, so it is tested on a small trace kept under `testdata/`.

Busy time is the union of a device's op intervals inside the window, averaged
over the devices used; idle share is 1 minus busy over the window. Device
time inside a span is the union of op intervals clipped to the union of the
spans of that name. An idle gap is labelled by the host span that covers
most of it, or "wait" where the host was mostly in none (waiting for
arrivals, or between calls).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Tuple

SPANS = ("window", "admit", "step", "switch")
Interval = Tuple[float, float]


def load(trace_dir: str, device_ids) -> dict:
    """{"devices": {id: [[start_ns, end_ns, "program/op"], ...]},
        "host": [[name, start_ns, end_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            try:
                did = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if did not in device_ids:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
            ops_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            ops, j = [], 0
            for e in sorted(ops_line.events, key=lambda e: e.start_ns) if ops_line else []:
                while j < len(mods) and mods[j][1] < e.start_ns:
                    j += 1
                prog = mods[j][2].split("(")[0] if j < len(mods) and mods[j][0] <= e.start_ns else "?"
                ops.append([e.start_ns, e.start_ns + e.duration_ns, f"{prog}/{e.name}"])
            devices[did] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        host.append([e.name, e.start_ns, e.start_ns + e.duration_ns])
    return {"devices": devices, "host": host}


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    ev["devices"] = {int(k): v for k, v in ev["devices"].items()}
    return ev


def union(iv: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two unions of intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


class Reduced:
    def __init__(self, events: dict):
        wins = [(s, e) for n, s, e in events["host"] if n == "window"]
        if not wins or not events["devices"]:
            raise ValueError("trace holds no window span or no device ops")
        self.win = max(wins, key=lambda w: w[1] - w[0])
        self.window_s = (self.win[1] - self.win[0]) * 1e-9
        self.spans = defaultdict(list)
        for n, s, e in events["host"]:
            if n != "window":
                self.spans[n].append((s, e))
        self.spans = {n: union(v) for n, v in self.spans.items()}
        self.ops = events["devices"]
        self.busy = {d: union([(max(s, self.win[0]), min(e, self.win[1]))
                               for s, e, _ in ops if e > self.win[0] and s < self.win[1]])
                     for d, ops in self.ops.items()}

    @property
    def n_devices(self) -> int:
        return len(self.busy)

    @property
    def busy_s(self) -> float:
        return sum(sum(b - a for a, b in iv) for iv in self.busy.values()) * 1e-9 / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_in(self, span: str) -> float:
        """Device-busy seconds inside spans named `span`, mean per device."""
        sp = self.spans.get(span, [])
        return sum(overlap(iv, sp) for iv in self.busy.values()) * 1e-9 / self.n_devices

    def top_ops(self, k: int = 10) -> List[list]:
        tot: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for s, e, name in ops:
                s, e = max(s, self.win[0]), min(e, self.win[1])
                if e > s:
                    tot[name] += (e - s) * 1e-9 / self.n_devices
        return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The k longest gaps between device work (over all devices), each
        named by the host span that covers most of it."""
        gaps = []
        for iv in self.busy.values():
            edges = [self.win[0]] + [x for ab in iv for x in ab] + [self.win[1]]
            gaps += [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        every = union([iv for sp in self.spans.values() for iv in sp])
        out = []
        for length, a, b in sorted(gaps, reverse=True)[:k]:
            cover = {n: overlap([(a, b)], sp) for n, sp in self.spans.items()}
            cover["wait"] = length - overlap([(a, b)], every)
            out.append([max(cover, key=cover.get), length * 1e-9])
        return out
