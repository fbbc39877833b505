"""Training substrate tests: optimizer, compression, checkpoint/restart
fault tolerance, loss-goes-down."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.models.model import model_param_defs
from repro.models.params import init_params
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config
from repro.training.data import SyntheticDataset
from repro.training.grad_compress import CompressConfig, compress_grads, init_error_feedback
from repro.training.loop import LoopConfig, SimulatedFailure, train_loop
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm
from repro.training.train_step import TrainStepConfig, init_opt_state, make_train_step


def _tiny():
    cfg = reduced(get_config("h2o-danube-1.8b"))
    ec = make_exec_config(cfg, 1)
    defs = model_param_defs(cfg, ec)
    params = init_params(defs, jax.random.PRNGKey(0), jnp.float32)
    return cfg, ec, params


def test_adamw_decreases_quadratic():
    p = {"w": jnp.array([5.0, -3.0])}
    st_ = adamw_init(p)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, st_ = adamw_update(g, st_, p, cfg)
    assert float(jnp.abs(p["w"]).max()) < 0.1


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 10.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 99), block=st.sampled_from([64, 256]))
def test_grad_compression_error_feedback_unbiased(seed, block):
    """With error feedback, the accumulated compressed sum converges to the
    true gradient sum (1-bit-Adam-style property)."""
    key = jax.random.PRNGKey(seed)
    g = {"w": jax.random.normal(key, (300,))}
    cfg = CompressConfig(enabled=True, block=block)
    err = init_error_feedback(g)
    total_true = jnp.zeros(300)
    total_comp = jnp.zeros(300)
    for _ in range(30):
        deq, err = compress_grads(g, err, cfg)
        total_true += g["w"]
        total_comp += deq["w"]
    rel = float(jnp.linalg.norm(total_comp - total_true) / jnp.linalg.norm(total_true))
    assert rel < 0.02, rel


def test_train_step_loss_decreases():
    cfg, ec, params = _tiny()
    tcfg = TrainStepConfig(
        opt=AdamWConfig(lr=3e-3, warmup_steps=5), seq_chunk=16, block_q=16, block_k=16
    )
    step_fn, _ = make_train_step(cfg, ec, DEFAULT_RULES, None, tcfg)
    opt_state = init_opt_state(params, tcfg)
    ds = SyntheticDataset(cfg, batch=4, seq=32)
    losses = []
    for i in range(60):
        params, opt_state, m = step_fn(params, opt_state, ds.at(i))
        losses.append(float(m["loss"]))
    assert min(losses[-10:]) < losses[0] - 0.3, (losses[0], losses[-5:])
    assert np.isfinite(losses).all()


def test_checkpoint_restart_bitwise_identical(tmp_path):
    """Fault tolerance: crash at step 7, resume, end state must equal the
    uninterrupted run exactly."""
    cfg, ec, params0 = _tiny()
    tcfg = TrainStepConfig(opt=AdamWConfig(lr=1e-3), seq_chunk=16, block_q=16, block_k=16)
    ds = SyntheticDataset(cfg, batch=2, seq=32)

    def fresh():
        p = jax.tree_util.tree_map(jnp.copy, params0)
        return p, init_opt_state(p, tcfg)

    step_fn, _ = make_train_step(cfg, ec, DEFAULT_RULES, None, tcfg)

    d1 = str(tmp_path / "a")
    p, o = fresh()
    s_ref = train_loop(step_fn, p, o, ds, LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=d1))

    d2 = str(tmp_path / "b")
    p, o = fresh()
    with pytest.raises(SimulatedFailure):
        train_loop(step_fn, p, o, ds, LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=d2),
                   fail_at=7)
    # restart (new process would do exactly this)
    p, o = fresh()
    s_res = train_loop(step_fn, p, o, ds, LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=d2))
    assert s_res.resumed_from == 4
    for a, b in zip(
        jax.tree_util.tree_leaves(s_ref.params), jax.tree_util.tree_leaves(s_res.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_elastic_reshard(tmp_path):
    """A checkpoint written on one layout restores onto another (elastic)."""
    from repro.checkpoint.checkpoint import load_checkpoint, save_checkpoint

    tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones(8)}
    path = save_checkpoint(str(tmp_path), 3, tree, {"note": "elastic"})
    restored, step, meta = load_checkpoint(path, tree)
    assert step == 3 and meta["note"] == "elastic"
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


def test_compressed_training_still_converges():
    cfg, ec, params = _tiny()
    tcfg = TrainStepConfig(
        opt=AdamWConfig(lr=3e-3, warmup_steps=5),
        compress=CompressConfig(enabled=True, block=256),
        seq_chunk=16, block_q=16, block_k=16,
    )
    step_fn, _ = make_train_step(cfg, ec, DEFAULT_RULES, None, tcfg)
    opt_state = init_opt_state(params, tcfg)
    ds = SyntheticDataset(cfg, batch=4, seq=32)
    losses = []
    for i in range(60):
        params, opt_state, m = step_fn(params, opt_state, ds.at(i))
        losses.append(float(m["loss"]))
    assert min(losses[-10:]) < losses[0] - 0.3
