"""Multi-device integration tests (subprocess: each check needs its own
XLA host-device count, which must be set before jax initializes)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(check: str, ndev: int = 8) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.testing.multidev_checks", check, str(ndev)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"{check} failed:\n{out.stdout}\n{out.stderr}"
    assert f"OK {check}" in out.stdout
    return out.stdout


def test_weight_store_tp_invariance_and_zero_copy_switch():
    out = _run("weight_store")
    assert "logits identical across TP [1, 2, 4, 8]" in out
    assert "zero-copy rebind" in out


def test_moe_sharded_matches_local_oracle():
    _run("moe_sharded", 4)


def test_kv_migration_preserves_contents():
    out = _run("migration")
    assert "without a host copy" in out


def test_fault_aborts_are_transactional():
    """Mid-flight abort paths (docs/faults.md): interrupted switch rolls
    back, dying migration leaves the source intact, reload on a shrunken
    pool serves correct logits."""
    out = _run("fault_abort")
    assert "rolled back" in out
    assert "source cache intact" in out
    assert "shrunken pool" in out


def test_engine_serves_with_tp_switches():
    out = _run("engine")
    assert "switch" in out


def test_sharded_train_step_matches_single_device():
    _run("train_step", 4)
