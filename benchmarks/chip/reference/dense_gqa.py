"""Plain float32 reference of a dense GQA decoder (the Llama/Mistral block:
arXiv:2302.13971, arXiv:2310.06825), written from the published description
and importing nothing of the program under test.

    h = E[tokens]
    per layer:  x = rmsnorm(h) * g1
                q, k, v = x Wq, x Wk, x Wv; rotary embedding on q and k
                (first half / second half of each head rotated as a pair)
                causal attention, each group of heads sharing one key/value
                head, keys limited to the sliding window where there is one
                h = h + attn Wo
                x = rmsnorm(h) * g2
                h = h + (silu(x Wg) * (x Wu)) Wd
    logits = (rmsnorm(h) * g) Wout

It runs one layer at a time, making each layer's weights again from the
seed (`weights.layer_weights`), so it never holds more than one layer in
float32. Matmuls run at "highest" precision, as float32 on a TPU needs.

`precision="fp8"` is the control: every projection's operands are rounded to
float8 e4m3 (weights per output channel, activations per token, each scaled
to the format's range) before a float32 product, the step below the bf16
the configurations state.

This module is also everything the harness knows of the architecture
(`harness.ARCH`): the shapes of a configuration (`dims`), the engine's
configuration (`program_config`), the engine's seeded weights
(`engine_params`), the reference's logits (`logits`) and the work a step
needs (`counts.py`), plus `compile_layer` for `compile_check.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

import counts
import weights
from counts import Dims
from weights import base_key, layer_weights, outer_weights

E4M3_MAX = 448.0
Q_CHUNK = 256


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, precision):
    """einsum of activations `x` and weights `w` whose first axes contract."""
    if precision == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.einsum(spec, x, w)


def _rmsnorm(x, dev, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + dev)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * inv  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "eps", "theta", "precision"))
def _layer(h, w, pos, *, m: Dims, eps, theta, precision):
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    B, S, _ = h.shape
    g = m.heads // m.kv_heads
    x = _rmsnorm(h, w["norm_attn"], eps)
    q = _rope(_mm("bsd,dnh->bsnh", x, w["wq"], precision), pos, theta)
    k = _rope(_mm("bsd,dnh->bsnh", x, w["wk"], precision), pos, theta)
    v = _mm("bsd,dnh->bsnh", x, w["wv"], precision)
    q = q.reshape(B, S, m.kv_heads, g, m.head_dim)
    outs = []
    for c0 in range(0, S, Q_CHUNK):  # query blocks bound the score matrix
        qc = q[:, c0:c0 + Q_CHUNK]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qc, k) * m.head_dim ** -0.5
        qi = jnp.arange(c0, c0 + qc.shape[1])[:, None]
        ki = jnp.arange(S)[None, :]
        mask = ki <= qi
        if m.window is not None:
            mask &= qi - ki < m.window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        outs.append(jnp.einsum("bkgqs,bskh->bqkgh", p, v))
    o = jnp.concatenate(outs, 1).reshape(B, S, m.heads, m.head_dim)
    h = h + _mm("bsnh,nhd->bsd", o, w["wo"], precision)
    x = _rmsnorm(h, w["norm_mlp"], eps)
    a = jax.nn.silu(_mm("bsd,df->bsf", x, w["w_gate"], precision))
    u = _mm("bsd,df->bsf", x, w["w_up"], precision)
    return h + _mm("bsf,fd->bsd", a * u, w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("m", "eps", "precision"))
def _head(h, ow, *, m: Dims, eps, precision):
    x = _rmsnorm(h, ow["final_norm"].astype(jnp.float32), eps)
    return _mm("nd,dv->nv", x, ow["lm_head"].astype(jnp.float32), precision)


_make_layer = jax.jit(layer_weights, static_argnames=("m",))
_make_outer = jax.jit(outer_weights, static_argnames=("m",))


def logits(seed: int, cfg: dict, seqs, rows, device, precision: str = "f32"):
    """Reference logits at chosen positions of each sequence.

    seqs: token-id arrays (prompt followed by the served tokens but the last);
    rows: for each sequence, the positions whose next-token logits to return.
    Returns one (len(rows[i]), vocab) float32 array per sequence.
    """
    m, eps, theta = dims(cfg), cfg["rms_norm_eps"], float(cfg["rope_theta"])
    S = -(-max(len(s) for s in seqs) // Q_CHUNK) * Q_CHUNK
    tok = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        tok[i, : len(s)] = s  # padding sits after each sequence: causal, unseen
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        key = base_key(seed)
        ow = _make_outer(key, m)
        h = jnp.take(ow["embed"], jnp.asarray(tok), axis=0).astype(jnp.float32)
        ow = {"final_norm": ow["final_norm"], "lm_head": ow["lm_head"]}
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (len(seqs), S))
        for i in range(m.layers):
            h = _layer(h, _make_layer(key, m, i), pos, m=m, eps=eps, theta=theta,
                       precision=precision)
        flat = np.concatenate([np.asarray(r) + i * S for i, r in enumerate(rows)])
        out = np.asarray(_head(h.reshape(-1, m.d_model)[flat], ow, m=m, eps=eps,
                               precision=precision))
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)


# ---------------------------------------------------------------------------
# what the harness asks of a configuration's module
# ---------------------------------------------------------------------------
def dims(cfg: dict) -> Dims:
    """The shapes of an HF configuration of this architecture."""
    heads = cfg["num_attention_heads"]
    return Dims(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        window=cfg.get("sliding_window"),
    )


def program_config(name: str, cfg: dict, program):
    """The engine's `ModelConfig`; `program` is the engine's configuration
    module (`repro.configs.base`), handed in by the harness so that nothing
    here imports the program."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError(f"{name}: dense_gqa draws an untied head")
    m = dims(cfg)
    return program.ModelConfig(
        name=name, family="dense", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        vocab_size=m.vocab, norm_eps=cfg["rms_norm_eps"],
        attn=program.AttnSpec(kind="swa" if m.window else "full", window=m.window,
                              rope_theta=float(cfg["rope_theta"])),
    )


def engine_params(seed: int, cfg: dict, mcfg):
    """The engine's parameter tree from the seed, in bfloat16, with the
    vocabulary padded to the engine's `vocab_padded`."""
    return weights.engine_params(base_key(seed), dims(cfg), mcfg.vocab_padded)


prefill_flops = counts.prefill_flops
decode_flops = counts.decode_flops
decode_bytes = counts.decode_bytes
weight_bytes = counts.weight_bytes


def compile_layer(cfg: dict, device, batch: int, seq_len: int):
    """One float32 layer of the reference, compiled for `device` at `batch`
    sequences of `seq_len`, from shapes alone."""
    one = SingleDeviceSharding(device)
    m = dims(cfg)
    w = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
         for k, (s, _) in weights.layer_shapes(m).items()}
    with jax.default_matmul_precision("highest"):
        return _layer.lower(
            jax.ShapeDtypeStruct((batch, seq_len, m.d_model), jnp.float32, sharding=one), w,
            jax.ShapeDtypeStruct((batch, seq_len), jnp.int32, sharding=one),
            m=m, eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]), precision="f32",
        ).compile()
