"""chip_smoke.py and the serving path it drives, rehearsed on the CPU at
reduced width: the platform refusal, the serve-and-check phases on one and
on four (virtual) devices, and the engine's TP-switch guards."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.launch.serve import build_engine, make_requests, switch_schedule
from repro.serving.engine import EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_ECONF = EngineConfig(n_slots=4, max_len=128, prefill_buckets=(32, 64))
SMALL_RUN = dict(n_requests=6, max_new=12, prompt_lens=(8, 60), switch_every=3)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cfg():
    """h2o-danube-1.8b at reduced width: 4 KV heads so TP 4 shards them, and
    the published window, which the 128-token slots never reach (as the
    full config's 2048-token slots never reach its 4096 window)."""
    full = get_config("h2o-danube-1.8b")
    return dataclasses.replace(
        reduced(full), num_heads=8, num_kv_heads=4, attn=full.attn
    )


def _env(ndev=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    env.pop("XLA_FLAGS", None)
    if ndev:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    return env


def test_main_refuses_a_non_tpu_platform():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=_env(), timeout=300, cwd=ROOT,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_serve_and_check_one_device(capsys):
    res = _chip_smoke().serve_and_check(
        small_cfg(), jax.devices()[:1], dataclasses.replace(SMALL_ECONF, candidate_tps=(1,)),
        **SMALL_RUN,
    )
    assert len(res["generated"]) == SMALL_RUN["n_requests"]
    assert all(len(g) == SMALL_RUN["max_new"] for g in res["generated"].values())
    assert res["max_dev"] < 0.05
    assert res["switch_log"] == []
    assert "teacher-forced max logit deviation" in capsys.readouterr().out


@pytest.fixture(scope="module")
def served():
    """Two short requests served at TP 1 with logits recorded."""
    cfg = small_cfg()
    econf = dataclasses.replace(SMALL_ECONF, candidate_tps=(1,), record_logits=True)
    eng = build_engine(cfg, jax.devices()[:1], econf, seed=0)
    eng.warmup()
    done = eng.run(make_requests(cfg, 2, 4, 0, (8, 20)))
    return cfg, done, eng.logit_trace


def _deviation(served, params, trace=None):
    cfg, done, logit_trace = served
    return _chip_smoke().teacher_forced_deviation(
        cfg, jax.devices()[0], params, done, trace or logit_trace, 128
    )


def test_serve_and_check_detects_wrong_weights(served):
    """The teacher-forced check fails an engine whose weights differ from
    the reference's: a different seed for the reference."""
    cs = _chip_smoke()
    params = cs.reference_params(served[0], jax.devices()[0], 1, jnp.bfloat16)
    assert _deviation(served, params) > cs.LOGIT_TOL


def test_logit_tolerance_separates_bf16_from_float8_weights(served):
    """The control behind LOGIT_TOL: the engine's own bf16 weights pass, the
    same weights rounded to float8 e4m3 for the reference fail."""
    cs = _chip_smoke()
    params = cs.reference_params(served[0], jax.devices()[0], 0, jnp.bfloat16)
    assert _deviation(served, params) < cs.LOGIT_TOL
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype), params
    )
    assert _deviation(served, rounded) > cs.LOGIT_TOL


def test_teacher_forced_check_fails_on_a_nan_logit(served):
    cs = _chip_smoke()
    params = cs.reference_params(served[0], jax.devices()[0], 0, jnp.bfloat16)
    trace = {rid: list(v) for rid, v in served[2].items()}
    rid = next(iter(trace))
    trace[rid][-1] = np.where(np.arange(trace[rid][-1].size) == 3, np.nan, trace[rid][-1])
    with pytest.raises(RuntimeError, match=f"request {rid}: non-finite logits"):
        _deviation(served, params, trace)


def test_compare_runs_fails_on_a_nan_logit():
    cs = _chip_smoke()
    logits = np.ones((3, 8), np.float32)
    run = {"generated": {0: [1, 2, 3]}, "logits": {0: logits}}
    assert cs.compare_runs(run, run) == (0.0, 1)
    bad = {"generated": run["generated"], "logits": {0: logits.copy()}}
    bad["logits"][0][1, 5] = np.nan
    with pytest.raises(RuntimeError, match="request 0: non-finite logits"):
        cs.compare_runs(bad, run)


def test_four_device_switch_phase_matches_fixed_tp1():
    code = (
        "import jax, chip_smoke, test_chip_smoke as t\n"
        "assert len(jax.devices()) == 4\n"
        "chip_smoke.run_phases(t.small_cfg(), jax.devices(), t.SMALL_ECONF, **t.SMALL_RUN)\n"
    )
    env = _env(4)
    env["PYTHONPATH"] += os.pathsep + os.path.join(ROOT, "tests")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600, cwd=ROOT,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "switch TP 1->2" in out.stdout and "switch TP 4->1" in out.stdout
    assert "switched vs fixed TP 1" in out.stdout


def test_switch_tp_rejects_a_tp_without_mesh():
    cfg = small_cfg()
    eng = build_engine(cfg, jax.devices()[:1], SMALL_ECONF, seed=0)
    assert eng.tps == [1]
    with pytest.raises(ValueError, match="no mesh for TP 2"):
        eng.switch_tp(2)
    assert eng.switch_tp(1) == {"rebind_s": 0.0, "migrate_s": 0.0}


def test_admit_refuses_a_prompt_longer_than_the_largest_bucket():
    from repro.serving.request import Request

    cfg = small_cfg()
    eng = build_engine(cfg, jax.devices()[:1], SMALL_ECONF, seed=0)
    longest = max(SMALL_ECONF.prefill_buckets)
    req = Request(0, "strict", np.zeros(longest + 1, np.int32), 4)
    with pytest.raises(ValueError, match=f"prompt length {longest + 1} exceeds buckets"):
        eng.admit(req)
    assert len(eng.slots.free) == SMALL_ECONF.n_slots  # the refused prompt holds no slot
    assert eng.slot_req == [None] * SMALL_ECONF.n_slots


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_goes_to_one_directory(tmp_path, env_dir):
    """Entries land in $JAX_COMPILATION_CACHE_DIR when it is set, else in
    .jax_cache at the root of the checkout holding the code (a copy here)."""
    checkout = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "src", "repro"), checkout / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = _env()
    env["PYTHONPATH"] = str(checkout / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "env_cache")
    code = (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "from repro.launch.serve import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300, cwd=tmp_path)
    assert out.returncode == 0, out.stderr
    want = tmp_path / "env_cache" if env_dir else checkout / ".jax_cache"
    assert out.stdout.split() == [str(want)]
    assert any(want.iterdir())
    # nothing else was written beside the checkout, nor inside it
    assert {p.name for p in tmp_path.iterdir()} == {"checkout"} | (
        {"env_cache"} if env_dir else set())
    assert (checkout / ".jax_cache").exists() != env_dir


@pytest.mark.parametrize(
    "tps,every,want",
    [([1], 8, {}), ([1, 2, 4], 8, {8: 2, 16: 4, 24: 1}), ([1, 2], 3, {3: 2, 6: 1})],
)
def test_switch_schedule_visits_engine_tps(tps, every, want):
    assert switch_schedule(tps, every) == want


def test_make_requests_two_tiers_in_range():
    cfg = small_cfg()
    reqs = make_requests(cfg, 10, 5, seed=3, prompt_lens=(8, 60))
    assert {r.tier for r in reqs} == {"strict", "relaxed"}
    assert all(8 <= r.prompt_len <= 60 for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < cfg.vocab_size for r in reqs)
    again = make_requests(cfg, 10, 5, seed=3, prompt_lens=(8, 60))
    assert all((a.prompt == b.prompt).all() for a, b in zip(reqs, again))
