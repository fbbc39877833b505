"""GPipe-style pipeline parallelism over a `pipe` mesh axis.

For 123B-class training where even FSDP×TP leaves the per-chip residency
tight, the period stack can additionally be partitioned into pipeline
stages: stage s owns periods [s·P/S, (s+1)·P/S); microbatches stream
through stages with activations handed over by `jax.lax.ppermute`.

Implementation: the classic shard_map schedule — run `n_micro + n_stages-1`
ticks; in each tick every stage processes the microbatch it holds (or a
bubble) and ppermutes its output to the next stage. Stage-local parameters
arrive pre-sharded over the `pipe` axis (leading period dim), so the mesh
(pipe, data, model) composes with every other axis rule.

This is the training-side scale-out option promised in DESIGN.md §4; the
dry-run exercises it via `rules=pp` on the biggest dense config, and
tests/test_pipeline.py checks numerical equality with the non-pipelined
stack on a host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(
    body: Callable,  # (h, stage_params, period_idx_within_stage) -> h
    params_stacked,  # pytree, leaves (n_periods, ...) — sharded over 'pipe'
    h0,  # (n_micro, B_micro, S, D) microbatched activations
    mesh: Mesh,
    n_periods: int,
    in_spec: P = P(None, ("data",), None, None),
):
    """Returns h after all periods, microbatched: (n_micro, B_micro, S, D)."""
    n_stages = mesh.shape["pipe"]
    assert n_periods % n_stages == 0
    periods_per_stage = n_periods // n_stages
    n_micro = h0.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fn(params_loc, h_all):
        """Runs on every (pipe) stage; h_all: local copy of microbatches."""
        sid = jax.lax.axis_index("pipe")
        # strip the leading pipe-shard dim from params (shard_map gives
        # (periods_per_stage, ...) already — leading dim is local)
        buf = h_all  # (n_micro, Bm, S, D): stage 0 reads, others ignore
        out = jnp.zeros_like(h_all)
        carry = jnp.zeros_like(h_all[0])

        def tick(state, t):
            carry, out = state
            mb = t - sid  # microbatch index this stage works on
            active = (mb >= 0) & (mb < n_micro)
            # stage 0 loads a fresh microbatch; others use the carry
            h_in = jnp.where(
                sid == 0,
                buf[jnp.clip(mb, 0, n_micro - 1)],
                carry,
            )
            h_out = h_in
            for k in range(periods_per_stage):
                h_out = body(h_out, jax.tree_util.tree_map(lambda x: x[k], params_loc), k)
            h_out = jnp.where(active, h_out, h_in)
            # last stage records its finished microbatch
            out = jnp.where(
                (sid == n_stages - 1) & active,
                out.at[jnp.clip(mb, 0, n_micro - 1)].set(h_out),
                out,
            )
            carry_next = jax.lax.ppermute(h_out, "pipe", fwd_perm)
            return (carry_next, out), None

        (carry, out), _ = jax.lax.scan(tick, (carry, out), jnp.arange(n_ticks))
        # only the last stage wrote real outputs (zeros elsewhere): psum
        # broadcasts them so the result is replicated over 'pipe'
        return jax.lax.psum(out, "pipe")

    pspec = P("pipe")
    out = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: pspec, params_stacked),
            in_spec,
        ),
        out_specs=in_spec,
        check_vma=False,
    )(params_stacked, h0)
    # only the last stage holds real outputs; psum-broadcast is unnecessary
    # for training (loss is computed on the last stage) but makes the
    # function referentially transparent for tests:
    return out


def make_pipe_mesh(devices, n_stages: int, tp: int = 1) -> Mesh:
    import numpy as np

    n = len(devices)
    assert n % (n_stages * tp) == 0
    arr = np.array(devices).reshape(n_stages, n // (n_stages * tp), tp)
    return Mesh(arr, ("pipe", "data", "model"))
