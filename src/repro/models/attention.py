"""Attention: GQA with full / sliding-window / local-global(+softcap) variants.

Prefill uses a blockwise (flash-style) streaming softmax over KV blocks
inside a scan over Q blocks — activation memory is O(S·block), which makes
long prefills compilable at 16 GB/chip. Decode is a single-token gather over
the cache; with ``kv_seq -> data`` sharding rules the same code becomes
context-parallel split-KV decode (XLA inserts the LSE-combining all-reduces).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, softcap
from repro.models.params import ParamDef
from repro.parallel.sharding import ExecConfig, pspec_for, shard_constraint

NEG_INF = -1e30
LANES = 128  # a TPU vector register's lanes: the minor tile of every layout
CACHE_AXES = ("batch", "act_kv", "kv_seq", "head_dim")  # one layer's K or V


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

    def q_step(_, q_in):
        q_i, qp = q_in  # (B,bq,KV,G,hd), (bq,)

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
    """q: (B,KV,G,hd); caches: (B,KV,S,hd); valid: (B,S) bool -> (B,KV,G,hd).

    Under `kv_seq -> data` rules this is split-KV (context-parallel) decode:
    the softmax max/sum and the PV contraction reduce over the sharded S axis
    and XLA lowers them to all-reduces over 'data'.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bkgh,bksh->bkgs", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s = shard_constraint(s, ("batch", "act_kv", None, "kv_seq"), rules, mesh)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum(
        "bkgs,bksh->bkgh", p / jnp.maximum(l, 1e-30), v_cache,
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
    positions,  # (S,) for prefill; (B,) for decode
    window: Optional[int],
    mode: str,  # prefill | decode
    cache: Optional[dict] = None,  # decode: {"k": (L,B,KV,Sc,hd), "v": ...} over periods
    layer=None,  # decode: this layer's index into the stacked cache
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, dict]:
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

    if mode == "prefill":
        S = x.shape[1]
        qg = q.reshape(B, S, KV, G, hd)
        o = _blockwise(
            qg, k, v, positions, positions,
            window=window, cap=cap, block_q=block_q, block_k=block_k,
        ).astype(x.dtype)
        with jax.named_scope("kv_write"):  # stored head-major: (B,KV,S,hd)
            kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            if window is not None and S > window:
                slots = swa_cache_slots(window, S)
                kh, vh = (jnp.zeros((B, KV, window, hd), c.dtype).at[:, :, slots].set(
                    c[:, :, -window:]) for c in (kh, vh))
            new_cache = {"k": kh, "v": vh}
        o = o.reshape(B, S, ec.heads_exec, hd)
    else:
        assert cache is not None and layer is not None
        Sc = cache["k"].shape[3]
        if window is not None:
            slot = positions % window
            written_all = positions >= window
            valid = (jnp.arange(Sc)[None] <= positions[:, None]) | written_all[:, None]
        else:
            slot = positions
            valid = jnp.arange(Sc)[None] <= positions[:, None]
        with jax.named_scope("kv_write"):  # one row per slot, in place
            new_cache = {
                n: _write_rows(cache[n], r[:, 0].astype(cache[n].dtype), slot, layer, rules, mesh)
                for n, r in (("k", k), ("v", v))
            }
        # read after the write: the slot's own row is part of its attention
        ck, cv = (jax.lax.dynamic_index_in_dim(new_cache[n], layer, 0, keepdims=False)
                  for n in ("k", "v"))
        qg = q[:, 0].reshape(B, KV, G, hd)
        o = decode_attention(qg, ck, cv, valid, cap, rules, mesh)
        o = o.astype(x.dtype).reshape(B, 1, ec.heads_exec, hd)

    y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    y = shard_constraint(y, ("res_batch", "seq", "embed"), rules, mesh)
    return y, new_cache


def _write_rows(cache, rows, slot, layer, rules, mesh):
    """`cache` (L,B,KV,Sc,hd), stacked over periods, with each slot's row of
    `rows` (B,KV,hd) written in place at [layer, b, :, slot[b]].

    The scan over layers carries the cache in the layout its body asks for,
    and copies the whole stack in and out where that is not the stored one.
    So the write's window and the attention's read must both keep (Sc, hd)
    minor: the attention's two products read each (b, kv) slab as (Sc, hd)
    tiles, and a write whose window spans the heads, (KV, hd), would pin the
    heads minor instead. Where head_dim fills whole 128-lane tiles the rows
    are scattered one `hd` row per (slot, head); else they are written slot
    by slot, which asks for no layout. The row-by-row writes run per device,
    so no slot's row crosses chips (tests/test_chip_compile.py checks both
    for a v5e).
    """
    if rows.shape[-1] % LANES == 0:
        B, KV = rows.shape[:2]
        return cache.at[
            layer, jnp.arange(B)[:, None], jnp.arange(KV)[None, :], slot[:, None]
        ].set(rows)

    def by_row(c, r, s, layer):
        for b in range(r.shape[0]):
            c = jax.lax.dynamic_update_slice(c, r[b][None, None, :, None], (layer, b, 0, s[b], 0))
        return c

    spec = pspec_for(("periods",) + CACHE_AXES, rules, mesh)
    if mesh is None or spec[3] is not None:  # a sharded sequence: left to the partitioner
        return by_row(cache, rows, slot, layer)
    return jax.shard_map(
        by_row, mesh=mesh,
        in_specs=(spec, P(spec[1], spec[2], spec[4]), P(spec[1]), P()),
        out_specs=spec, check_vma=False,
    )(cache, rows, slot, layer)


def attn_cache_defs(cfg: ModelConfig, ec: ExecConfig, batch: int, seq_len: int, window):
    """Cache ParamDefs for one attention layer (no leading period dim)."""
    Sc = min(window, seq_len) if window is not None else seq_len
    shape = (batch, ec.kv_exec, Sc, cfg.head_dim)
    return {
        "k": ParamDef(shape, CACHE_AXES, init="zeros"),
        "v": ParamDef(shape, CACHE_AXES, init="zeros"),
    }
