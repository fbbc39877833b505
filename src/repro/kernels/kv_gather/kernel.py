"""Aggregated, pipelined paged-KV gather/scatter (Nitsum §3.2.2).

The paper's KV-migration bottleneck is fragmentation: paged KV lives in many
small non-contiguous pages, and per-page copies serialize. Its fix is
aggregate-into-staging + double-buffered overlap of copy and transmit.

TPU-native form: a Pallas kernel whose grid walks the page list (scalar-
prefetched indices); the BlockSpec index map addresses the source page in
HBM directly, and Pallas's automatic multi-buffered DMA pipeline *is* the
paper's double buffer — the HBM read of page i+1 overlaps the staging write
of page i. The contiguous staging buffer then feeds a single large ICI
collective (see core/migration.py).

gather:  staged[i] = pool[page_ids[i]]         (fragmented -> contiguous)
scatter: pool[page_ids[i]] = staged[i]         (contiguous -> fragmented)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rows(F: int):
    """Tile view of one F-element page: (F // 128, 128) when F fills whole
    lanes, else (1, F). Either way the block's last two dims equal the
    array's, which is what the TPU compiler accepts for any F."""
    lanes = 128 if F % 128 == 0 else F
    return F // lanes, lanes


def _copy_kernel(ids_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def kv_gather_p(pool, page_ids, *, interpret: bool):
    """pool: (P, F); page_ids: (n,) int32 -> staged (n, F)."""
    n = page_ids.shape[0]
    P, F = pool.shape
    r, lanes = _rows(F)
    staged = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, r, lanes), lambda i, ids: (ids[i], 0, 0))],
            out_specs=pl.BlockSpec((1, r, lanes), lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, r, lanes), pool.dtype),
        interpret=interpret,
    )(jnp.asarray(page_ids, jnp.int32), pool.reshape(P, r, lanes))
    return staged.reshape(n, F)


def _scatter_kernel(ids_ref, pool_ref, staged_ref, out_ref):
    del pool_ref  # present only for the output alias
    out_ref[...] = staged_ref[...]


def kv_scatter_p(pool, staged, page_ids, *, interpret: bool):
    """pool: (P, F); staged: (n, F) -> pool with pool[page_ids[i]] = staged[i].

    The pool is donated/aliased: untouched pages keep their contents.
    """
    n = page_ids.shape[0]
    P, F = pool.shape
    r, lanes = _rows(F)
    dst = pl.BlockSpec((1, r, lanes), lambda i, ids: (ids[i], 0, 0))
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                dst,  # pool (aliased with the output)
                pl.BlockSpec((1, r, lanes), lambda i, ids: (i, 0, 0)),  # staged
            ],
            out_specs=dst,
        ),
        out_shape=jax.ShapeDtypeStruct((P, r, lanes), pool.dtype),
        input_output_aliases={1: 0},  # pool -> out (index counts the scalar)
        interpret=interpret,
    )(jnp.asarray(page_ids, jnp.int32), pool.reshape(P, r, lanes),
      staged.reshape(n, r, lanes))
    return out.reshape(P, F)
