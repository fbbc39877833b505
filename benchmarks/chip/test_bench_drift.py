"""The served end-to-end entries of a tiered open cell, and the fixture's
open cell on four CPU devices, with heads of 16 and of 80 (the row-by-row
cache write), switching TP 4 <-> 1 and checked against the reference, with
a request across each switch in the sample."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import check
import fixture
import harness

HERE = Path(__file__).resolve().parent
YI = "yi-34b-stage8.decode-batch"
SERVED = {"ttft_p90_ms", "tpot_p90_ms", "goodput_rps"}


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "per_layer"])
def test_served_entries_name_files_and_serve_only_the_open_cell(tmp_path, trace):
    root, bench, spec = fixture.make(tmp_path, 4)
    kind, want = ("metrics", {"fixture_admits"}) if trace else ("e2e", SERVED | {"setup_s"})
    assert {m["name"] for m in harness.metrics_for(spec, "tiny.open", trace)} == want
    assert not SERVED & {m["name"] for m in harness.metrics_for(spec, YI, trace)}
    for name in want:
        assert hasattr(harness.module(kind, name, bench), "read")


FOUR = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {here!r})
import check, fixture
fixture.TINY.update({variant!r})
seen = []
sample = check.sample

def spied(finished, records, switch_s, *a):
    chosen = sample(finished, records, switch_s, *a)
    span = lambda r, t: records[r.req_id].first_s < t < records[r.req_id].last_s
    seen.append([[t, any(span(r, t) for r in chosen), any(span(r, t) for r in finished)]
                 for t in switch_s])
    return chosen

check.sample = spied
res = fixture.run(Path(tempfile.mkdtemp()), "tiny.open", seconds=2.5, chips=4, control=True)
print(json.dumps({{"res": res, "switches": seen[0]}}))
"""
VARIANTS = {"heads16": {}, "heads80": {"hidden_size": 640, "num_attention_heads": 8}}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_four_devices_served_metrics_and_switches_checked(variant):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join([str(fixture.ROOT / "src"), env.get("PYTHONPATH", "")])
    src = FOUR.format(here=str(HERE), variant=VARIANTS[variant])
    run = subprocess.run([sys.executable, "-c", src], capture_output=True, text=True, env=env,
                         timeout=600, cwd=fixture.ROOT)
    assert run.returncode == 0, run.stderr[-3000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    res = out["res"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["correct"] is True, res["check"]
    limit = res["check"]["widest_logit_gap"]["limit"]
    assert limit == check.DEFAULT_GAP_LIMIT
    assert res["check"]["widest_logit_gap"]["value"] <= limit
    assert set(res["metrics"]) == SERVED | {"setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    spanned = [s for s in out["switches"] if s[2]]
    assert len(spanned) >= 2, out["switches"]  # both directions, each crossed by a request
    assert all(s[1] for s in spanned)  # and each such switch has one in the sample
    assert res["control"]["correct"] is False  # the float8 reference in the engine's place
    assert res["control"]["widest_logit_gap"] > limit and res["control"]["limit"] == limit
