"""Public jit'd wrapper: block-shape selection + CPU interpret fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.tp_shard_matmul.kernel import tp_shard_matmul_p


def _pick_block(dim: int, candidates=(512, 256, 128, 64, 32, 16, 8)) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return dim


def _lane_block(dim: int, full: int) -> int:
    """Block for a lane (last) dim: a multiple of 128 that divides `dim`, or
    `dim` itself when it spans the whole array dim `full` — the only blocks
    the TPU compiler accepts, held to on every backend."""
    for c in (512, 384, 256, 128):
        if dim % c == 0:
            return c
    if dim == full:
        return dim
    raise ValueError(
        f"tp_shard_matmul: a {dim}-wide shard of a {full}-wide weight cannot "
        "be tiled in lane blocks that are multiples of 128"
    )


def pick_blocks(m: int, k: int, n_out: int, n_store: int, mode: str):
    """(bm, bn, bk) for x (m, k) against a weight with n_store columns."""
    n_full = n_store if mode == "col" else n_out
    return (
        _pick_block(m),
        _lane_block(n_out, n_full),
        _lane_block(k, k),
    )


def _interpret() -> bool:
    """Pallas runs interpreted only on the CPU backend."""
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit, static_argnames=("mode", "n_out", "bm", "bn", "bk", "interpret")
)
def _call(x, w_store, offset, *, mode, n_out, bm, bn, bk, interpret):
    return tp_shard_matmul_p(
        x, w_store, offset, mode=mode, n_out=n_out, bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )


def tp_shard_matmul(x, w_store, offset, *, n_out: int, mode: str = "col"):
    """y = x @ (execution-time-selected shard of w_store).

    x: (M, K). col mode: w_store (K, N_store), selects n_out cols at offset.
    row mode: w_store (K_store, n_out), selects K rows at offset.
    offset must be a multiple of the chosen weight block (guaranteed when
    shard sizes divide by the block; ops picks blocks that divide n_out/K).
    Raises ValueError for a column shard that no legal lane block divides.
    """
    m, k = x.shape
    bm, bn, bk = pick_blocks(m, k, n_out, w_store.shape[1], mode)
    return _call(
        x, w_store, jnp.asarray(offset, jnp.int32),
        mode=mode, n_out=n_out, bm=bm, bn=bn, bk=bk, interpret=_interpret(),
    )
