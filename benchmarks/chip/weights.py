"""Seeded weights of a dense GQA decoder, made on the device.

Every matrix of every layer draws from a key of its own, folded from the
seed, the matrix's name and the layer's index. `engine_params` makes the
whole tree the serving engine takes in one jitted call, in the type it is
served in; `layer_weights` makes one layer alone, so that the float32
reference can go layer by layer without holding the model. Neither takes
anything from the program: the benchmark hands the engine these weights.

Norm weights are stored as their deviation from 1 (the published weight is
1 + deviation), which is also how the engine stores them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from counts import Dims

NAMES = ("embed", "lm_head", "final_norm", "norm_attn", "wq", "wk", "wv", "wo",
         "norm_mlp", "w_gate", "w_up", "w_down")
NORM_STD = 0.1


def base_key(seed: int) -> jnp.ndarray:
    """A raw threefry key from any whole-number seed, 64 bits and more."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _draw(key, name: str, layer, shape, std, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer_shapes(m: Dims) -> dict:
    d, h, kv, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return {
        "norm_attn": ((d,), NORM_STD),
        "wq": ((d, h, hd), d ** -0.5),
        "wk": ((d, kv, hd), d ** -0.5),
        "wv": ((d, kv, hd), d ** -0.5),
        "wo": ((h, hd, d), (h * hd) ** -0.5),
        "norm_mlp": ((d,), NORM_STD),
        "w_gate": ((d, f), d ** -0.5),
        "w_up": ((d, f), d ** -0.5),
        "w_down": ((f, d), f ** -0.5),
    }


def layer_weights(key, m: Dims, layer, dtype=jnp.bfloat16) -> dict:
    return {n: _draw(key, n, layer, s, std, dtype) for n, (s, std) in layer_shapes(m).items()}


def outer_weights(key, m: Dims, dtype=jnp.bfloat16) -> dict:
    d, v = m.d_model, m.vocab
    return {
        "embed": _draw(key, "embed", 0, (v, d), 1.0, dtype),
        "lm_head": _draw(key, "lm_head", 0, (d, v), d ** -0.5, dtype),
        "final_norm": _draw(key, "final_norm", 0, (d,), NORM_STD, dtype),
    }


@functools.partial(jax.jit, static_argnames=("m", "vocab_padded"))
def engine_params(key, m: Dims, vocab_padded: int):
    """The engine's parameter tree (layers stacked on a leading axis, the
    vocabulary padded with zero rows), in bfloat16."""
    lw = jax.vmap(lambda i: layer_weights(key, m, i))(jnp.arange(m.layers))
    ow = outer_weights(key, m)
    pad = vocab_padded - m.vocab
    return {
        "embed": jnp.pad(ow["embed"], ((0, pad), (0, 0))),
        "lm_head": jnp.pad(ow["lm_head"], ((0, 0), (0, pad))),
        "final_norm": ow["final_norm"],
        "periods": {"pos0": {
            "norm1": lw["norm_attn"],
            "mixer": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": lw["norm_mlp"],
            "ffn": {"w_gate": lw["w_gate"], "w_in": lw["w_up"], "w_out": lw["w_down"]},
        }},
    }
