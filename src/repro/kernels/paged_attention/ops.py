"""jit'd wrapper for paged flash-decode."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention_p


def _interpret() -> bool:
    """Pallas runs interpreted only on the CPU backend."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def _call(q, k_pages, v_pages, block_tables, seq_lens, softcap, interpret):
    return paged_decode_attention_p(
        q, k_pages, v_pages, block_tables, seq_lens,
        softcap=softcap, interpret=interpret,
    )


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *, softcap=None):
    """Single-token decode attention over paged KV.

    q: (B, KV, G, hd); k/v_pages: (num_pages, page_size, KV, hd);
    block_tables: (B, n_pages) int32; seq_lens: (B,) int32.
    """
    return _call(
        q, k_pages, v_pages,
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        softcap, _interpret(),
    )
