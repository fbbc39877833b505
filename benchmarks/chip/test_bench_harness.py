"""The harness on the CPU at a tiny size: discovery by name of a
configuration, a mix and a metric that no harness file names; the result
line of a run with and without tracing; and the refusals."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import fixture
import harness
import peaks


def test_discovery_of_a_fixture_config_mix_and_metric(tmp_path):
    root, bench, spec = fixture.make(tmp_path)
    assert harness.config_of(spec, "tiny", root)["hidden_size"] == fixture.TINY["hidden_size"]
    assert harness.mix_of("tiny-open", bench)["arrivals"] == "open"
    names = [m["name"] for m in harness.metrics_for(spec, "tiny.open", True)]
    assert "fixture_admits" in names and "decode_step_ms.batch" not in names
    assert {m["name"] for m in harness.metrics_for(spec, "tiny.open", False)} == {
        "ttft_p90_ms", "tpot_p90_ms", "goodput_rps", "setup_s"}
    assert {m["name"] for m in harness.metrics_for(spec, "tiny.batch", False)} == {
        "tokens_per_s", "setup_s"}


def test_traced_run_reports_the_fixture_metric(tmp_path):
    res = fixture.run(tmp_path, "tiny.open", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["fixture_admits"]["value"] > 0
    assert res["metrics"]["fixture_admits"]["unit"] == "1"
    assert not any(k.endswith(".batch") for k in res["metrics"])  # the batch cell's own
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "check"


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    res = fixture.run(tmp_path, "tiny.batch")
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["check"]["tokens_compared"]["value"] >= 1
    json.dumps(res)


def test_run_exits_nonzero_and_prints_nothing_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(fixture.HERE, "run.py"), "--workload",
         "yi-34b-stage8.decode-batch", "--seed", str(2**35), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=fixture.ROOT,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_unknown_device_kind_is_refused(monkeypatch, capsys):
    import jax

    import run

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99 imaginary", id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    rc = run.main(["--workload", "yi-34b-stage8.decode-batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no published peaks" in out.err
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
