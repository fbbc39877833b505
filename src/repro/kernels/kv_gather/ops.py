"""jit'd wrappers for the KV gather/scatter kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.kv_gather.kernel import kv_gather_p, kv_scatter_p


def _interpret() -> bool:
    """Pallas runs interpreted only on the CPU backend."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather(pool, page_ids, interpret):
    return kv_gather_p(pool, page_ids, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def _scatter(pool, staged, page_ids, interpret):
    return kv_scatter_p(pool, staged, page_ids, interpret=interpret)


def kv_gather(pool, page_ids):
    """Aggregate fragmented KV pages into a contiguous staging buffer.

    pool: (num_pages, F) — flattened page payloads; page_ids: (n,) int32.
    Returns staged (n, F).
    """
    return _gather(pool, jnp.asarray(page_ids, jnp.int32), _interpret())


def kv_scatter(pool, staged, page_ids):
    """Write a contiguous staging buffer back into (donated) pool pages."""
    return _scatter(pool, staged, jnp.asarray(page_ids, jnp.int32), _interpret())
