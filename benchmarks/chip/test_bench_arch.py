"""A configuration brings its architecture as one module under `reference/`:
the readings of the dense GQA module are pinned, a second module runs
through the harness with no harness file edited, a configuration's own gap
limit is the one applied, and a module that is missing or incomplete fails
in `build` by name."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import check
import fixture
import harness

HERE = Path(__file__).resolve().parent
SEED = 2**33 + 5
YI = json.loads((HERE / "configs" / "yi-34b-stage8.json").read_text())
CONFIGS = {
    "tiny": fixture.TINY,
    # Yi's heads, head size, vocabulary and rope theta; 2 layers of width 256
    "yi-cut": dict(YI, num_hidden_layers=2, hidden_size=256, head_dim=128, intermediate_size=512),
}
CONTEXTS = [1, 100, 1000, 2047]
# Recorded from the harness and reference as they were before the module
# interface (`harness.dims_of`, `harness.program_config`,
# `weights.engine_params(base_key(seed), dims, vocab_padded)`,
# `dense_gqa.logits(seed, dims, eps, theta, ...)` and `counts.*`).
PINNED = {
    "tiny": {
        "engine_params": "acf1c61a0cfc46b6", "logits_f32": "9653265e46b941be",
        "logits_fp8": "79c7c3854b5a1755",
        "prefill_flops": [721920.0, 10314752.0, 184811520.0, 1102467072.0],
        "decode_flops": 6107136.0, "decode_bytes": [2334976.0, 4501504.0],
        "weight_bytes": 722176,
    },
    "yi-cut": {
        "engine_params": "87b31f0424a91c32", "logits_f32": "db2f4c1c160fd12f",
        "logits_fp8": "a4a242ec802cf163",
        "prefill_flops": [51175424.0, 353492992.0, 6616776704.0, 47083520000.0],
        "decode_flops": 384991232.0, "decode_bytes": [76911104.0, 230273024.0],
        "weight_bytes": 51120640,
    },
}


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype.name)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_gqa_readings_are_pinned(name):
    cfg = CONFIGS[name]
    arch = harness.arch_of(cfg)
    m = arch.dims(cfg)
    params = arch.engine_params(SEED, cfg, harness.program_config(arch, name, cfg))
    seq = np.random.default_rng(SEED % 2**63).integers(0, m.vocab, 40).astype(np.int32)
    lg = {p: arch.logits(SEED, cfg, [seq], [np.arange(24, 40)], jax.devices()[0], p)[0]
          for p in ("f32", "fp8")}
    got = {
        "engine_params": digest(jax.tree_util.tree_leaves(params)),
        "logits_f32": digest([lg["f32"]]), "logits_fp8": digest([lg["fp8"]]),
        "prefill_flops": [arch.prefill_flops(m, L) for L in (1, 17, 256, 1000)],
        "decode_flops": arch.decode_flops(m, CONTEXTS),
        "decode_bytes": [arch.decode_bytes(m, CONTEXTS, groups=g) for g in (1, 4)],
        "weight_bytes": arch.weight_bytes(m),
    }
    assert got == PINNED[name]


# A second architecture: dense_gqa's functions under another name, each call
# written down, and no `compile_layer`.
WRAPPED = '''"""dense_gqa under another name, writing down each call."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location("_dense", Path(__file__).with_name("dense_gqa.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
CALLS = Path(__file__).with_suffix(".calls")


def _wrap(name):
    def call(*a, **kw):
        with CALLS.open("a") as f:
            f.write(name + "\\n")
        return getattr(_dense, name)(*a, **kw)
    return call


for _name in {names!r}:
    globals()[_name] = _wrap(_name)
'''

SECOND = """
import json, sys, time
from pathlib import Path
root, bench = Path({root!r}), Path({bench!r})
import jax
import breakdown, compile_check, fixture, harness
spec = json.loads((root / "BENCHMARK.json").read_text())
cell = harness.cell_of(spec, "wrapped.batch")
calls = bench / "reference" / "wrapped.calls"


def called():
    names = calls.read_text().split() if calls.exists() else []
    calls.unlink(missing_ok=True)
    return sorted(set(names))


with fixture.no_persistent_cache():
    res = harness.run_cell(spec, cell, {seed}, 2.0, True, jax.devices()[:1], time.perf_counter(),
                           root=root, bench=bench)
    by_run = called()
    breakdown.measure(spec, cell, {seed}, 1.0, jax.devices()[:1], root=root, bench=bench)
    by_breakdown = called()
    compile_check.benchmark_programs("wrapped", harness.config_of(spec, "wrapped", root),
                                     jax.devices()[0])
files = [getattr(m, "__file__", None) or "" for m in list(sys.modules.values())]
print(json.dumps({{"res": res, "run": by_run, "breakdown": by_breakdown, "files": files}}))
"""


def test_a_second_architecture_needs_only_a_module_a_config_and_a_mix(tmp_path):
    root, bench, spec = fixture.make(tmp_path)
    (bench / "reference" / "wrapped.py").write_text(WRAPPED.format(names=harness.ARCH))
    (bench / "configs" / "wrapped.json").write_text(
        json.dumps(dict(fixture.TINY, name="wrapped", reference="wrapped")))
    (bench / "traffic" / "wrapped-batch.json").write_text(json.dumps(fixture.BATCH))
    spec["configs"].append({"name": "wrapped", "source": "fixture", "reduced": [], "why": "fixture",
                            "file": "benchmarks/chip/configs/wrapped.json"})
    spec["workloads"].append({"name": "wrapped.batch", "config": "wrapped",
                              "traffic": "wrapped-batch", "chips": 1, "why": "fixture"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.batch" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["wrapped.batch"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(bench), str(fixture.ROOT / "src")]))
    src = SECOND.format(root=str(root), bench=str(bench), seed=SEED)
    run = subprocess.run([sys.executable, "-c", src], capture_output=True, text=True, env=env,
                         timeout=600, cwd=root)
    assert run.returncode == 0, run.stderr[-3000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    res = out["res"]
    assert res["correct"] is True and res["check"]["tokens_compared"]["value"] > 0
    want = {m["name"] for m in harness.metrics_for(spec, "wrapped.batch", True)}
    assert "decode_step_ms.batch" in set(res["metrics"]) <= want  # the per-layer line
    # every function of the module, by the run; the bytes decode needs are read
    # against a device trace, which on the CPU only `breakdown.py`'s reduction has
    assert set(out["run"]) == set(harness.ARCH) - {"decode_bytes"}
    assert "decode_bytes" in out["breakdown"]
    assert "wrapped reference layer: skipped" in run.stdout  # no compile_layer: a logged line
    assert not [f for f in out["files"] if f.startswith(str(HERE))]  # only the copy was read


@pytest.mark.parametrize("given,limit", [(0.25, 0.25), (None, 0.06)], ids=["own", "default"])
def test_the_configurations_gap_limit_judges_the_program_and_the_control(tmp_path, monkeypatch,
                                                                       given, limit):
    chk = dict(fixture.TINY["check"])
    if given is not None:
        chk["gap_limit"] = given
    monkeypatch.setattr(fixture, "TINY", dict(fixture.TINY, check=chk))
    real = check.widest_gap

    def between(seed, arch, cfg, reqs, device, precision="f32", against=None):
        # the gaps read, set between the two limits: 0.10 served, 0.20 control
        _, n, lg = real(seed, arch, cfg, reqs, device, precision, against)
        return (0.10 if precision == "f32" else 0.20), n, lg

    monkeypatch.setattr(check, "widest_gap", between)
    res = fixture.run(tmp_path, "tiny.batch", control=True)
    assert res["check"]["widest_logit_gap"] == {"value": 0.10, "limit": limit}
    assert res["control"]["limit"] == limit and res["control"]["widest_logit_gap"] == 0.20
    assert res["correct"] is (0.10 <= limit) and res["control"]["correct"] is (0.20 <= limit)


PARTIAL = '''"""Only part of what the harness asks for."""


def dims(cfg):
    return None


def logits(seed, cfg, seqs, rows, device, precision="f32"):
    return []
'''


@pytest.mark.parametrize("reference,message", [
    ("absent", r"reference/absent\.py does not exist"),
    ("partial", r"reference/partial\.py lacks program_config, engine_params, prefill_flops, "
                r"decode_flops, decode_bytes, weight_bytes"),
], ids=["no_file", "missing_functions"])
def test_a_missing_or_incomplete_module_fails_in_build_by_name(tmp_path, reference, message):
    root, bench, spec = fixture.make(tmp_path)
    (bench / "reference" / "partial.py").write_text(PARTIAL)
    cfg = dict(fixture.TINY, reference=reference)
    with pytest.raises(ValueError, match=message):
        harness.build("tiny", cfg, fixture.BATCH, jax.devices()[:1], SEED, bench)
