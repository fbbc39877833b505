"""Hypothesis property tests on the weight store's shard-selection algebra
(single-device: the layout math, not the mesh execution — that is covered by
the multidev checks)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.parallel.sharding import make_exec_config
from repro.profiles.profiler import ProfileTable


@settings(max_examples=40, deadline=None)
@given(
    n_units=st.sampled_from([8, 16, 32, 64]),
    pool_log=st.integers(2, 4),
    s_log=st.integers(0, 2),
    tp_log=st.integers(0, 4),
)
def test_storage_layout_covers_every_exec_shard(n_units, pool_log, s_log, tp_log):
    """For any (pool, storage_tp, exec_tp) with s <= tp <= pool and tp <=
    n_units: the execution shard of every device must lie inside its storage
    shard — the invariant that makes TP switching zero-copy."""
    N = 2 ** pool_log
    s = 2 ** s_log
    tp = 2 ** tp_log
    if not (s <= tp <= N and tp <= n_units and s <= n_units):
        return
    for d in range(N):
        # device d holds storage shard floor(d*s/N); model-major exec mesh
        # gives it model coordinate t = floor(d*tp/N)
        q = (d * s) // N
        t = (d * tp) // N
        store_lo = q * (n_units // s)
        store_hi = store_lo + n_units // s
        width = max(n_units // tp, 1)
        exec_lo = (t * n_units) // tp
        exec_hi = exec_lo + width
        assert store_lo <= exec_lo and exec_hi <= store_hi, (
            f"d={d} N={N} s={s} tp={tp} n={n_units}: exec [{exec_lo},{exec_hi}) "
            f"outside storage [{store_lo},{store_hi})"
        )


@settings(max_examples=30, deadline=None)
@given(tp=st.sampled_from([1, 2, 4, 8, 16]))
def test_exec_config_grouping_invariants(tp):
    """GQA grouping stays uniform at every TP level for every arch."""
    from repro.configs import ASSIGNED_ARCHS

    for name in ASSIGNED_ARCHS:
        cfg = get_config(name)
        if cfg.family == "ssm":
            continue
        ec = make_exec_config(cfg, tp)
        assert ec.heads_exec % tp == 0
        assert ec.heads_exec % ec.kv_exec == 0
        assert ec.kv_exec % min(cfg.num_kv_heads, ec.kv_exec) == 0
        # block replication: kv_exec is kv or tp, never in between
        assert ec.kv_exec in (cfg.num_kv_heads, tp)


def test_profile_table_roundtrip(tmp_path):
    t = ProfileTable()
    t.decode_s[(2, 4, 64)] = 0.01
    t.prefill_s[(2, 32)] = 0.05
    p = str(tmp_path / "prof.json")
    t.save(p)
    t2 = ProfileTable.load(p)
    assert t2.decode_s == {(2, 4, 64): 0.01}
    assert t2.prefill_time(64, 2) == pytest.approx(0.1)


def test_tabulated_perf_model_falls_back():
    from repro.profiles.profiler import TabulatedPerfModel

    cfg = get_config("llama3-8b")
    t = ProfileTable()
    t.decode_s[(2, 8, 1024)] = 0.012
    m = TabulatedPerfModel(cfg, t)
    assert m.decode_step_time_s(8, 1024, 2) == pytest.approx(0.012)
    # tp without a table entry falls back to the analytic model
    assert m.decode_step_time_s(8, 1024, 4) > 0
