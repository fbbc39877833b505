"""Attention: GQA with full / sliding-window / local-global(+softcap) variants.

Train/prefill use a blockwise (flash-style) streaming softmax over KV blocks
inside a scan over Q blocks — activation memory is O(S·block), which makes the
32k prefill shapes compilable at 16 GB/chip. Decode is a single-token gather
over the cache; with ``kv_seq -> data`` sharding rules the same code becomes
context-parallel split-KV decode (XLA inserts the LSE-combining all-reduces),
which is how ``long_500k`` runs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, softcap
from repro.models.params import ParamDef
from repro.parallel.sharding import ExecConfig, shard_constraint

NEG_INF = -1e30


def attn_param_defs(cfg: ModelConfig, ec: ExecConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    defs = {
        "wq": ParamDef((d, ec.heads_exec, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, ec.kv_exec, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, ec.kv_exec, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef(
            (ec.heads_exec, hd, d), ("heads", "head_dim", "embed"),
            scale=(cfg.num_heads * hd) ** -0.5,  # fan-in is heads x head_dim
        ),
    }
    if cfg.attn.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="zeros")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="zeros")
    return defs


def _qk_norm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def _blockwise(q, k, v, q_pos, k_pos, *, window, cap, block_q, block_k):
    """q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd); positions: (Sq,), (Sk,).

    Returns (B,Sq,KV,G,hd).
    """
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq != 0:  # odd small shapes: single block
        bq = Sq
    if Sk % bk != 0:
        bk = Sk
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5

    qb = q.reshape(B, nq, bq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(nq, bq)
    kb = k.reshape(B, nk, bk, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bk, KV, hd).transpose(1, 0, 2, 3, 4)
    kpb = k_pos.reshape(nk, bk)

    @jax.checkpoint  # recompute the KV scan in bwd: avoids saving every
    # (bq x bk) softmax block — the difference between O(S·bq) and O(S²/blk)
    # attention residency under layer-level remat
    def q_step(_, q_in):
        q_i, qp = q_in  # (B,bq,KV,G,hd), (bq,)

        @jax.checkpoint  # flash-bwd: recompute s/p per block in the backward
        # pass instead of saving score-sized f32 residuals (the dominant HBM
        # term otherwise — see EXPERIMENTS.md §Perf)
        def kv_step(carry, kv_in):
            m, l, acc = carry
            k_j, v_j, kp = kv_in  # (B,bk,KV,hd), (bk,)
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", q_i, k_j, preferred_element_type=jnp.float32
            ) * scale
            if cap is not None:
                s = cap * jnp.tanh(s / cap)
            mask = qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= (qp[:, None] - kp[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bkgqs,bskh->bkgqh", p, v_j, preferred_element_type=jnp.float32
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,bq,hd)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,bq,KV,G,hd)

    _, outs = jax.lax.scan(q_step, None, (qb, qpb))  # (nq,B,bq,KV,G,hd)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, hd)


def decode_attention(q, k_cache, v_cache, valid, cap, rules, mesh):
    """q: (B,KV,G,hd); caches: (B,S,KV,hd); valid: (B,S) bool -> (B,KV,G,hd).

    Under `kv_seq -> data` rules this is split-KV (context-parallel) decode:
    the softmax max/sum and the PV contraction reduce over the sharded S axis
    and XLA lowers them to all-reduces over 'data'.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bkgh,bskh->bkgs", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s = shard_constraint(s, ("batch", "act_kv", None, "kv_seq"), rules, mesh)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum(
        "bkgs,bskh->bkgh", p / jnp.maximum(l, 1e-30), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o


def swa_cache_slots(window: int, seq_len: int):
    """Rotating-buffer slot for each of the last `window` absolute positions."""
    start = max(seq_len - window, 0)
    pos = jnp.arange(start, seq_len)
    return pos % window


def attn_apply(
    p,
    x,
    *,
    cfg: ModelConfig,
    ec: ExecConfig,
    rules,
    mesh,
    positions,  # (S,) for train/prefill; (B,) for decode
    window: Optional[int],
    mode: str,  # train | prefill | decode
    cache: Optional[dict] = None,  # {"k": (B,Sc,KV,hd), "v": ...} for decode
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    B = x.shape[0]
    hd = cfg.head_dim
    KV, G = ec.kv_exec, ec.q_per_kv
    cap = cfg.attn.logit_softcap

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    if cfg.attn.qk_norm:
        q = _qk_norm(q, p["q_norm"])
        k = _qk_norm(k, p["k_norm"])
    q = shard_constraint(q, ("batch", "seq", "act_heads", "head_dim"), rules, mesh)
    k = shard_constraint(k, ("batch", "seq", "act_kv", "head_dim"), rules, mesh)
    v = shard_constraint(v, ("batch", "seq", "act_kv", "head_dim"), rules, mesh)

    if mode == "decode":
        rope_pos = positions[:, None]  # (B,1)
    else:
        rope_pos = positions[None, :]  # (1,S)
    q = apply_rope(q, rope_pos, cfg.attn.rope_theta)
    k = apply_rope(k, rope_pos, cfg.attn.rope_theta)

    if mode in ("train", "prefill"):
        S = x.shape[1]
        qg = q.reshape(B, S, KV, G, hd)
        o = _blockwise(
            qg, k, v, positions, positions,
            window=window, cap=cap, block_q=block_q, block_k=block_k,
        ).astype(x.dtype)
        new_cache = None
        if mode == "prefill":
            if window is not None and S > window:
                slots = swa_cache_slots(window, S)
                ck = jnp.zeros((B, window, KV, hd), k.dtype).at[:, slots].set(
                    k[:, -window:]
                )
                cv = jnp.zeros((B, window, KV, hd), v.dtype).at[:, slots].set(
                    v[:, -window:]
                )
                new_cache = {"k": ck, "v": cv}
            else:
                new_cache = {"k": k, "v": v}
        o = o.reshape(B, S, ec.heads_exec, hd)
    else:
        assert cache is not None
        Sc = cache["k"].shape[1]
        if window is not None:
            slot = positions % window
            written_all = positions >= window
            valid = (jnp.arange(Sc)[None] <= positions[:, None]) | written_all[:, None]
        else:
            slot = positions
            valid = jnp.arange(Sc)[None] <= positions[:, None]
        k1 = k[:, 0]  # (B,KV,hd)
        v1 = v[:, 0]
        ck = jax.vmap(lambda c, s, val: jax.lax.dynamic_update_slice(c, val[None], (s, 0, 0)))(
            cache["k"], slot, k1
        )
        cv = jax.vmap(lambda c, s, val: jax.lax.dynamic_update_slice(c, val[None], (s, 0, 0)))(
            cache["v"], slot, v1
        )
        new_cache = {"k": ck, "v": cv}
        qg = q[:, 0].reshape(B, KV, G, hd)
        o = decode_attention(qg, ck, cv, valid, cap, rules, mesh)
        o = o.astype(x.dtype).reshape(B, 1, ec.heads_exec, hd)

    y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    y = shard_constraint(y, ("res_batch", "seq", "embed"), rules, mesh)
    return y, new_cache


def attn_cache_defs(cfg: ModelConfig, ec: ExecConfig, batch: int, seq_len: int, window):
    """Cache ParamDefs for one attention layer (no leading period dim)."""
    Sc = min(window, seq_len) if window is not None else seq_len
    shape = (batch, Sc, ec.kv_exec, cfg.head_dim)
    axes = ("batch", "kv_seq", "act_kv", "head_dim")
    return {
        "k": ParamDef(shape, axes, init="zeros"),
        "v": ParamDef(shape, axes, init="zeros"),
    }
