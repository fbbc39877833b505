"""The engine's spans and scopes through the trace reduction (`enginetrace`):
self time and engine-span gap labels on hand-made intervals, scopes read from
compiled HLO text, and `testdata/xla_cpu_engine_trace.json.gz`: the fixture's
`tiny.batch` cell on the CPU backend, recorded by
`breakdown.measure(..., keep=<path>)`, its XLA:CPU executor's op events
standing in for a device's ops; and `testdata/v5e_engine_trace.json.gz`,
five decode steps and an admit of a small engine (d_model 256) on one v5e,
loaded by `enginetrace.load` with the decode program's compiled text, and
a `window` span added around the engine's spans. On four virtual devices,
the `tiny.open` TP schedule's switch spans and the resharding program's
scope."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import enginetrace
import fixture
import harness
import tracereduce
from repro.serving.engine import SCOPES, SPANS

TESTDATA = Path(__file__).resolve().parent / "testdata"
ENGINE_TRACE = TESTDATA / "xla_cpu_engine_trace.json.gz"
OLD_TRACE = TESTDATA / "xla_cpu_trace.json.gz"
V5E_TRACE = TESTDATA / "v5e_engine_trace.json.gz"
DECODE = "jit_decode_step"


def test_self_times_of_nested_ops_by_hand():
    ops = [[0, 100, "p/unscoped/while"], [10, 30, "p/attention/a"], [30, 60, "p/mlp/m"],
           [60, 60, "p/mlp/empty"], [120, 150, "p/head/h"], [140, 175, "p/head/late"]]
    assert enginetrace.self_times(ops) == [50, 20, 30, 0, 20, 35]
    ev = {"devices": {0: ops}, "host": [["window", 0, 200, {}]]}
    r = enginetrace.EngineReduced(ev)
    assert sum(v for _, v in r.top_ops(100)) == pytest.approx(r.busy_s)
    assert r.top_ops(2) == [["p/unscoped/while", pytest.approx(50e-9)],
                            ["p/head/late", pytest.approx(35e-9)]]
    assert r.scope_s("p", ("attention",)) == pytest.approx(20e-9)
    assert r.program_s("p") == (pytest.approx(155e-9), pytest.approx(155e-9))
    assert r.top_ops(10, "head") == [["p/head/late", pytest.approx(35e-9)],
                                     ["p/head/h", pytest.approx(20e-9)]]


def test_idle_gaps_labelled_by_the_innermost_engine_span_by_hand():
    host = [["window", 0, 1000, {}], ["step", 100, 600, {}],
            ["engine.step", 105, 595, {"step_num": 7}], ["engine.decode", 110, 150, {}],
            ["engine.sync", 150, 400, {}], ["engine.logits", 400, 560, {}],
            ["engine.slots", 560, 590, {}], ["admit", 700, 800, {}]]
    ops = [[0, 100, "p/a/x"], [160, 390, "p/a/y"], [800, 1000, "p/a/z"]]
    r = enginetrace.EngineReduced({"devices": {0: ops}, "host": host})
    # 390..800 lies mostly in the driver's step (210 of 410): logits 160, slots 30, sync 10,
    # the engine step alone 5; 100..160: decode 40, sync 10, the engine step alone 5
    assert r.idle_gaps() == [["step/engine.logits", pytest.approx(410e-9)],
                             ["step/engine.decode", pytest.approx(60e-9)]]
    assert r.span_ms("engine.logits") == pytest.approx(160e-6)
    want = {"step": 10, "step/engine.step": 10, "step/engine.decode": 40, "step/engine.sync": 20,
            "step/engine.logits": 160, "step/engine.slots": 30, "wait": 100, "admit": 100}
    assert r.idle_by_span() == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    base = tracereduce.Reduced({"devices": {0: ops},
                                "host": [h[:3] for h in host if h[0] in tracereduce.SPANS]})
    assert [g[0].split("/")[0] for g in r.idle_gaps()] == [g[0] for g in base.idle_gaps()]
    assert enginetrace.idle_in(r, "step") == pytest.approx((500 - 230) * 1e-9)
    assert enginetrace.idle_in(base, "step") == enginetrace.idle_in(r, "step")


HLO = """HloModule jit_decode_step, is_scheduled=true

%fused_computation.3 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[4]{0} dynamic-update-slice(%param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/attention/kv_write/vmap()/dynamic_update_slice"}
}

%fused_computation.4 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %exp.2 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(decode_step)/while/body/closed_call/mlp/exp"}
  ROOT %copy.9 = f32[4]{0} copy(%exp.2)
}

ENTRY %main.5 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %dus_fusion = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %exp_fusion = f32[4]{0} fusion(%dus_fusion), kind=kLoop, calls=%fused_computation.4
  %while.2 = f32[4]{0} while(%exp_fusion), condition=%c, body=%b, metadata={op_name="jit(decode_step)/while"}
  ROOT %dot.1 = f32[4]{0} dot(%while.2, %p), metadata={op_name="jit(decode_step)/head/bsd,dv->bsv/dot_general"}
}
"""


def test_scopes_read_from_compiled_hlo_text():
    got = enginetrace.scopes_from_hlo([HLO], SCOPES)
    assert got[(DECODE, "dus_fusion")] == "kv_write"  # the root's op_name
    assert got[(DECODE, "exp_fusion")] == "mlp"  # the computation's only scope
    assert got[(DECODE, "while.2")] == enginetrace.UNSCOPED
    assert got[(DECODE, "dot.1")] == "head"
    lab = lambda prog, op: enginetrace.label(prog, op, got, SCOPES)  # noqa: E731
    assert lab(DECODE, "dot.1") == f"{DECODE}/head/dot.1"
    assert lab(DECODE, "nowhere") == f"{DECODE}/?/nowhere"
    assert lab("jit_migrate", "all-to-all.1") == "jit_migrate/migrate/all-to-all.1"
    assert enginetrace.innermost("jit(f)/attention/kv_write/vmap()/x", SCOPES) == "kv_write"


@pytest.mark.parametrize("trace", [OLD_TRACE, ENGINE_TRACE], ids=lambda p: p.name)
def test_the_driver_span_numbers_read_as_before(trace):
    ev = tracereduce.read(str(trace))
    base = tracereduce.Reduced(
        dict(ev, host=[h[:3] for h in ev["host"] if h[0] in tracereduce.SPANS]))
    r = enginetrace.EngineReduced(ev)
    assert r.idle_share == base.idle_share and r.busy_s == base.busy_s
    for span in ("step", "admit", "switch"):
        assert r.device_in(span) == base.device_in(span)
    assert [g[0].split("/")[0] for g in r.idle_gaps()] == [g[0] for g in base.idle_gaps()]
    assert [g[1] for g in r.idle_gaps()] == [g[1] for g in base.idle_gaps()]
    # self times add up to busy time; the old sum of durations counts nested ops twice
    assert sum(v for _, v in r.top_ops(10**6)) == pytest.approx(r.busy_s, rel=1e-9)
    assert sum(v for _, v in r.top_ops()) <= r.busy_s * (1 + 1e-9)


def _inside(inner, outer):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outer)


def test_recorded_engine_trace_holds_every_span_nested_with_its_stats():
    ev = tracereduce.read(str(ENGINE_TRACE))
    by = {n: [h for h in ev["host"] if h[0] == n] for n in SPANS + tracereduce.SPANS}
    switch = ("engine.switch", "engine.rebind", "engine.migrate")  # on four devices, below
    assert all(by[n] for n in SPANS if n not in switch)
    parent = {"engine.prefill": "engine.admit", "engine.insert": "engine.admit",
              "engine.first_token": "engine.admit", "engine.decode": "engine.step",
              "engine.sync": "engine.step", "engine.logits": "engine.step",
              "engine.slots": "engine.step", "engine.step": "step", "engine.admit": "admit"}
    for child, outer in parent.items():
        assert all(_inside(h, by[outer]) for h in by[child]), child
    nums = [h[3]["step_num"] for h in by["engine.step"]]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    assert all(isinstance(h[3]["req_id"], int) for h in by["engine.admit"])
    assert all(h[3] == {} for h in by["engine.decode"])
    # inside one step: dispatch, wait, logits fetch, slot loop, in that order
    order = ("engine.decode", "engine.sync", "engine.logits", "engine.slots")
    for st in by["engine.step"]:
        kids = sorted((h for n in order for h in by[n] if _inside(h, [st])), key=lambda h: h[1])
        assert tuple(h[0] for h in kids) == order


def _mask(intervals, lo, hi, res):
    m = np.zeros(int((hi - lo) / res) + 1, bool)
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            m[int((a - lo) / res):int((b - lo) / res)] = True
    return m


def _ctx(ev, r):
    steps = [h for h in ev["host"] if h[0] == "step"]
    lo = r.win[0]
    calls = SimpleNamespace(steps=[((s - lo) * 1e-9, (e - lo) * 1e-9, 1, []) for _, s, e in
                                   (h[:3] for h in steps)], admits=[])
    return SimpleNamespace(calls=calls, seconds=r.window_s, trace=r, chips=1, peaks=None)


def test_recorded_engine_trace_against_a_sampled_count():
    import breakdown

    ev = tracereduce.read(str(ENGINE_TRACE))
    r = enginetrace.EngineReduced(ev)
    ctx = _ctx(ev, r)
    got = breakdown.engine_readings(ctx)
    lo, hi = r.win
    res = 100.0  # ns
    ops = ev["devices"][0]
    busy = _mask([(s, e) for s, e, _ in ops], lo, hi, res)
    step = _mask([h[1:3] for h in ev["host"] if h[0] == "step"], lo, hi, res)
    n = len(ctx.calls.steps)
    assert got["steps"] == n > 0
    want_gap = (step & ~busy).sum() * res * 1e-6 / n
    assert got["decode_host_gap_ms"] == pytest.approx(want_gap, rel=0.02)
    metric = harness.module("metrics", "decode_host_gap_ms.batch")
    assert metric.read(ctx) == pytest.approx(got["decode_host_gap_ms"])
    logits = [h[2] - h[1] for h in ev["host"] if h[0] == "engine.logits" and lo <= h[1] < hi]
    assert got["logits_fetch_ms"] == pytest.approx(np.mean(logits) * 1e-6)
    # self time by sampling: each sample goes to the latest-started op covering it
    t = lo + res * (np.arange(int((hi - lo) / res)) + 0.5)
    owner = np.full(t.shape, -1)
    start = np.full(t.shape, -np.inf)
    for i, (s, e, _) in enumerate(ops):
        a, b = np.searchsorted(t, [s, e])
        later = start[a:b] <= s
        owner[a:b][later], start[a:b][later] = i, s
    scope = np.array([o[2].split("/")[1] if o[2].startswith(DECODE) else "" for o in ops] + [""])
    for key, names in (("decode_attention_ms", ("attention", "kv_write")),
                       ("decode_mlp_ms", ("mlp",)), ("decode_unscoped_ms", ("unscoped",))):
        want = np.isin(scope[owner], names).sum() * res * 1e-6 / n
        assert got[key] == pytest.approx(want, rel=0.03, abs=2e-4), key
    assert got["decode_attention_ms"] > 0 and got["decode_mlp_ms"] > 0


@pytest.mark.parametrize("trace", [ENGINE_TRACE, V5E_TRACE], ids=lambda p: p.name)
def test_decode_program_ops_all_carry_a_scope_and_add_up_to_its_busy_time(trace):
    ev = tracereduce.read(str(trace))
    r = enginetrace.EngineReduced(ev)
    scopes = {n.split("/")[1] for n in r.self_s if n.startswith(DECODE + "/")}
    assert scopes <= set(SCOPES) | {enginetrace.UNSCOPED}
    assert {"embed", "attention", "kv_write", "mlp", "head"} <= scopes
    self_sum, busy = r.program_s(DECODE)
    assert self_sum == pytest.approx(busy, rel=1e-9) and busy > 0
    # the layer loop's event holds its body's ops: its self time is a part of its length
    lo, hi = r.win
    loops = [o for o in ev["devices"][0] if o[2].startswith(DECODE + "/unscoped/while")]
    length = sum(max(0, min(e, hi) - max(s, lo)) for s, e, _ in loops) * 1e-9
    assert 0 < sum(r.self_s[n] for n in {o[2] for o in loops}) < 0.5 * length
    idle = r.idle_by_span()
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    if trace == V5E_TRACE:  # engine spans only: the probe called the engine without the driver
        return
    assert sum(v for k, v in idle.items() if k.split("/")[0] == "step") == pytest.approx(
        enginetrace.idle_in(r, "step"), rel=1e-9)
    assert idle["step/engine.sync"] > 0 and idle["step/engine.logits"] > 0
    assert {n.split("/")[1] for n in r.self_s if n.startswith("jit_insert_cache/")} == {"kv_write"}


FOUR = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {here!r})
import jax
import breakdown, enginetrace, fixture, harness, tracereduce

tmp = Path(tempfile.mkdtemp())
root, bench, spec = fixture.make(tmp, 4)
cell = harness.cell_of(spec, "tiny.open")
keep = str(tmp / "events.json.gz")
with fixture.no_persistent_cache():
    res = breakdown.measure(spec, cell, 2**36 + 3, 1.3, jax.devices()[:4], root=root,
                            bench=bench, keep=keep)
ev = tracereduce.read(keep)
print(json.dumps({{"host": [h for h in ev["host"] if h[0] in
                            ("switch", "engine.switch", "engine.rebind", "engine.migrate")],
                  "spans": sorted({{h[0] for h in ev["host"]}}),
                  "labels": sorted({{o[2].rsplit("/", 1)[0] for ops in ev["devices"].values()
                                    for o in ops}}),
                  "devices": sorted(ev["devices"]), "gaps": res["breakdown"]["idle_gaps"]}}))
"""


def test_four_devices_switch_spans_and_the_migrate_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(fixture.ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join([src, env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", FOUR.format(here=str(fixture.HERE))],
                         capture_output=True, text=True, env=env, timeout=600, cwd=fixture.ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == [0, 1, 2, 3]
    assert set(SPANS) <= set(res["spans"])  # the engine as it is now opens every span
    by = {n: [h for h in res["host"] if h[0] == n] for n in
          ("switch", "engine.switch", "engine.rebind", "engine.migrate")}
    assert by["engine.switch"], "the schedule switched TP inside the window"
    for sw in by["engine.switch"]:
        assert _inside(sw, by["switch"])
        assert {sw[3]["from_tp"], sw[3]["to_tp"]} == {1, 4}
        kids = [h for n in ("engine.rebind", "engine.migrate") for h in by[n] if _inside(h, [sw])]
        assert [h[0] for h in sorted(kids, key=lambda h: h[1])] == ["engine.rebind",
                                                                    "engine.migrate"]
    migrate = [lab for lab in res["labels"] if lab.startswith("jit_migrate/")]
    assert migrate and all(lab == "jit_migrate/migrate" for lab in migrate)
    for prog in ("jit_decode_step", "jit_prefill_step"):
        assert f"{prog}/attention" in res["labels"] and f"{prog}/?" not in res["labels"]
    assert {f"jit_decode_step/{s}" for s in ("embed", "kv_write", "mlp", "head", "select")} <= set(
        res["labels"])
