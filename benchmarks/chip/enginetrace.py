"""The engine's own spans and named scopes in a profiler trace, on top of
`tracereduce`.

`load` reads the `.xplane.pb` as `tracereduce.load` does, and keeps more:
every `engine.*` host span with its stats (`req_id`, `step_num`, `from_tp`,
`to_tp`), and for each device op its scope, so that an op reads
`program/scope/op`. The scope is the innermost engine scope
(`repro.serving.engine.SCOPES`) in the op's `op_name`. A v5e trace names
each op by its HLO text without the metadata, so the `op_name`s come from
the compiled programs' `as_text()` (`scopes_from_hlo`), whose instruction
names are the trace's. The resharding program's ops come from the
partitioner and carry no `op_name`: a program named `jit_<scope>` gives its
scope to each op that has none. An op with an `op_name` outside every scope
is `unscoped`; one that no compiled text names is `?`. On the CPU
backend, where no device plane exists, the XLA:CPU executor's op events
(host threads, with `hlo_module`, `hlo_op` and `device_ordinal` stats) stand
in for a device's ops, so that the reduction is tested on a small recorded
trace under `testdata/`.

`EngineReduced` keeps `tracereduce.Reduced`'s busy time, idle share and
device time inside the driver's spans as they are, and adds:

- self time: each instant of device work goes to the innermost op running
  then (on a v5e, a loop's body ops run inside the `while` op's event), so
  self times add up to busy time;
- `top_ops` by self time, labelled `program/scope/op`;
- idle gaps labelled `<driver span>/<engine span>` by the engine span that
  is innermost over most of the gap (`step/engine.logits`), else as today.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

import tracereduce
from tracereduce import union

UNSCOPED = "unscoped"
UNKNOWN = "?"
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def innermost(op_name: str, scopes: Iterable[str]) -> str:
    """The last path element of `op_name` that is one of `scopes`."""
    scopes = set(scopes)
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def scopes_from_hlo(texts: Iterable[str], scopes: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """{(program, instruction): scope} from compiled programs' `as_text()`.
    An instruction without an `op_name` (a fusion the compiler made) takes
    the scope of the computation it calls: its root's, else the most common
    among that computation's instructions. Programs of one name (a prefill
    per bucket) share one map; the first text to name an instruction wins."""
    scopes = tuple(scopes)
    out: Dict[Tuple[str, str], str] = {}
    for text in texts:
        prog = _MODULE.search(text).group(1)
        comps: Dict[str, list] = {}  # computation -> [(name, op_name, callee, is_root)]
        body = None
        for line in text.splitlines():
            head = _COMP.match(line)
            if head and not _INSTR.match(line):
                body = comps.setdefault(head.group(1), [])
                continue
            ins = _INSTR.match(line)
            if ins and body is not None:
                op_name, callee = _OP_NAME.search(line), _CALLS.search(line)
                body.append((ins.group(2), op_name and op_name.group(1),
                             callee and callee.group(1), bool(ins.group(1))))
        memo: Dict[str, str] = {}

        def scope_of(op_name, callee):
            if op_name is not None:
                return innermost(op_name, scopes)
            return comp_scope(callee) if callee in comps else UNSCOPED

        def comp_scope(comp):
            if comp not in memo:
                memo[comp] = UNSCOPED  # a cycle reads as unscoped
                rows = comps[comp]
                root = [scope_of(o, c) for _, o, c, r in rows if r]
                found = [innermost(o, scopes) for _, o, _, _ in rows if o is not None]
                found = [f for f in found if f != UNSCOPED]
                memo[comp] = (root[0] if root and root[0] != UNSCOPED else
                              max(set(found), key=found.count) if found else UNSCOPED)
            return memo[comp]

        for rows in comps.values():
            for name, op_name, callee, _ in rows:
                out.setdefault((prog, name), scope_of(op_name, callee))
    return out


def label(prog: str, op: str, op_scopes: Dict[Tuple[str, str], str], scopes: Iterable[str]) -> str:
    """`program/scope/op`; the scope is `?` where no compiled text named the op."""
    scope = op_scopes.get((prog, op), UNKNOWN)
    if scope in (UNSCOPED, UNKNOWN) and prog.startswith("jit_") and prog[4:] in set(scopes):
        scope = prog[4:]
    return f"{prog}/{scope}/{op}"


def load(trace_dir: str, device_ids, op_scopes: Optional[dict] = None,
         scopes: Iterable[str] = ()) -> dict:
    """{"devices": {id: [[start_ns, end_ns, "program/scope/op"], ...]},
        "host": [[name, start_ns, end_ns, {stat: value}], ...]} from the
    newest trace: the driver's spans and every `engine.*` span."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    op_scopes = op_scopes or {}
    devices, host = defaultdict(list), []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            try:
                did = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if did not in device_ids:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
            j = 0
            for e in sorted(lines["XLA Ops"].events, key=lambda e: e.start_ns) if "XLA Ops" in lines else []:
                while j < len(mods) and mods[j][1] < e.start_ns:
                    j += 1
                prog = mods[j][2] if j < len(mods) and mods[j][0] <= e.start_ns else "?"
                op = e.name.split(" = ")[0].lstrip("%")
                devices[did].append([e.start_ns, e.start_ns + e.duration_ns,
                                     label(prog, op, op_scopes, scopes)])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    end = e.start_ns + e.duration_ns
                    if e.name in tracereduce.SPANS or e.name.startswith("engine."):
                        stats = {k: v for k, v in e.stats if not k.startswith("_")}
                        host.append([e.name, e.start_ns, end, stats])
                        continue
                    st = dict(e.stats)
                    if "hlo_op" in st and "device_ordinal" in st and st["device_ordinal"] in device_ids:
                        devices[st["device_ordinal"]].append(
                            [e.start_ns, end, label(st["hlo_module"], st["hlo_op"], op_scopes, scopes)])
    return {"devices": {d: sorted(v) for d, v in devices.items()}, "host": host}


def innermost_segments(ops: List[list]) -> List[Tuple[float, float, int]]:
    """(start, end, i): every stretch covered by some op, given to the
    innermost one running then, the latest started (then the shortest)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], ops[i][1]))
    pts = sorted({x for s, e, _ in ops for x in (s, e)})
    out = []
    heap: list = []
    k = 0
    for a, b in zip(pts, pts[1:]):
        while k < len(order) and ops[order[k]][0] <= a:
            i = order[k]
            heapq.heappush(heap, (-ops[i][0], ops[i][1], i))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def self_times(ops: List[list]) -> List[float]:
    """Each op's self time: its innermost segments' length."""
    out = [0.0] * len(ops)
    for a, b, i in innermost_segments(ops):
        out[i] += b - a
    return out


def _cover(segs, lo, hi, fill):
    """[(start, end, label)] covering [lo, hi]: `segs` (sorted, disjoint)
    with the stretches between them labelled `fill`."""
    out, t = [], lo
    for a, b, lab in segs:
        if a > t:
            out.append((t, a, fill))
        out.append((a, b, lab))
        t = b
    if hi > t:
        out.append((t, hi, fill))
    return out


def idle_in(reduced, span: str) -> float:
    """Device-idle seconds inside the window's spans named `span`, mean per
    device. Works on a `tracereduce.Reduced` as well."""
    lo, hi = reduced.win
    sp = [(max(a, lo), min(b, hi)) for a, b in reduced.spans.get(span, []) if b > lo and a < hi]
    inside = sum(b - a for a, b in sp) * 1e-9
    return inside - reduced.device_in(span) if sp else 0.0


class EngineReduced(tracereduce.Reduced):
    def __init__(self, events: dict):
        driver = [h[:3] for h in events["host"] if h[0] in tracereduce.SPANS]
        super().__init__(dict(events, host=driver))
        self.engine = sorted((h for h in events["host"] if h[0].startswith("engine.")),
                             key=lambda h: (h[1], -h[2]))
        lo, hi = self.win
        self.self_s: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            clipped = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
            for (_, _, name), t in zip(clipped, self_times(clipped)):
                self.self_s[name] += t * 1e-9 / self.n_devices

    def top_ops(self, k: int = 10, scope: Optional[str] = None) -> List[list]:
        """The k ops with the most self time in the window, mean per device."""
        rows = [(n, v) for n, v in self.self_s.items()
                if scope is None or n.split("/")[1:2] == [scope]]
        return [[n, v] for n, v in sorted(rows, key=lambda x: -x[1])[:k]]

    def scope_s(self, program: str, scopes: Iterable[str]) -> float:
        """Self seconds of `program`'s ops under any of `scopes`."""
        scopes = set(scopes)
        return sum(v for n, v in self.self_s.items()
                   if n.split("/")[0] == program and n.split("/")[1] in scopes)

    def program_s(self, program: str) -> Tuple[float, float]:
        """(sum of self times, union of op intervals) of `program`, seconds
        in the window, mean per device: equal where the reduction is sound."""
        lo, hi = self.win
        busy = sum(sum(b - a for a, b in union([(max(s, lo), min(e, hi)) for s, e, n in ops
                                                if n.split("/")[0] == program and e > lo and s < hi]))
                   for ops in self.ops.values()) * 1e-9 / self.n_devices
        return sum(v for n, v in self.self_s.items() if n.split("/")[0] == program), busy

    def span_ms(self, name: str) -> Optional[float]:
        """Mean duration of the `engine.*` spans named `name` that started
        in the window, ms."""
        sp = [h for h in self.engine if h[0] == name and self.win[0] <= h[1] < self.win[1]]
        return 1e3 * sum(e - s for _, s, e, _ in sp) * 1e-9 / len(sp) if sp else None

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds in the window, mean per device, by
        `<driver span>/<engine span>`: the driver span over each idle instant
        (else "wait") and the innermost engine span there, where one is."""
        lo, hi = self.win
        eng = [(max(s, lo), min(e, hi), n) for n, s, e, _ in self.engine if e > lo and s < hi]
        eng = _cover([(a, b, eng[i][2]) for a, b, i in innermost_segments(eng)], lo, hi, None)
        drv = _cover(sorted((a, b, n) for n, iv in self.spans.items() for a, b in iv
                            if b > lo and a < hi), lo, hi, "wait")
        out: Dict[str, float] = defaultdict(float)
        for iv in self.busy.values():
            idle = _cover([(a, b, False) for a, b in iv], lo, hi, True)
            pts = sorted({x for part in (idle, drv, eng) for a, b, _ in part for x in (a, b)})
            ptr = [0, 0, 0]
            for a, b in zip(pts, pts[1:]):
                labs = []
                for j, part in enumerate((idle, drv, eng)):
                    while part[ptr[j]][1] <= a:
                        ptr[j] += 1
                    labs.append(part[ptr[j]][2])
                if labs[0] and b > a:
                    name = labs[1] if labs[2] is None else f"{labs[1]}/{labs[2]}"
                    out[name] += (b - a) * 1e-9 / self.n_devices
        return dict(out)

    def engine_label(self, a: float, b: float) -> Optional[str]:
        """The engine span innermost over most of [a, b], or None."""
        sp = [(max(s, a), min(e, b), n) for n, s, e, _ in self.engine if e > a and s < b]
        if not sp:
            return None
        cover: Dict[str, float] = defaultdict(float)
        for (_, _, n), t in zip(sp, self_times(sp)):
            cover[n] += t
        return max(cover, key=cover.get)

    def idle_gaps(self, k: int = 10) -> List[list]:
        """`tracereduce`'s k longest gaps, their labels followed by the
        engine span innermost over most of each gap, where one is."""
        gaps = []  # as the base class finds them, in its order
        for iv in self.busy.values():
            edges = [self.win[0]] + [x for ab in iv for x in ab] + [self.win[1]]
            gaps += [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        out = []
        for (name, secs), (_, a, b) in zip(super().idle_gaps(k), sorted(gaps, reverse=True)):
            inner = self.engine_label(a, b)
            out.append([f"{name}/{inner}" if inner else name, secs])
        return out
