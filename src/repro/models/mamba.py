"""Mamba layers.

Mamba-2 (SSD / state-space duality, arXiv:2405.21060): chunked matmul-form
algorithm — intra-chunk attention-like term + inter-chunk state recurrence.
Mamba-1 (selective scan, used by Jamba): chunked associative scan.

Both are written against the logical-axis sharding rules: the inner dimension
(heads for v2, channels for v1) shards over the model axis; B/C projections
are group-shared and replicated. Decode is a single-step state update —
the "KV cache" analogue is the SSM state, which is what TP switching has to
migrate for these families (DESIGN.md §7).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.parallel.sharding import ExecConfig, shard_constraint


def causal_conv(x, w, tail: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. x: (B,S,C), w: (K,C), tail: (B,K-1,C) or None.

    Returns (y, new_tail) where new_tail is the last K-1 inputs.
    """
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)  # (B, S+K-1, C)
    S = x.shape[1]
    y = sum(w[k] * jax.lax.dynamic_slice_in_dim(xp, k, S, axis=1) for k in range(K))
    return y, xp[:, -(K - 1):]


def _gated_rmsnorm(y, z, scale, eps=1e-5):
    dt = y.dtype
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, -1, keepdims=True)
    return (y * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================
def mamba2_param_defs(cfg: ModelConfig) -> dict:
    m = cfg.mamba
    d, d_in = cfg.d_model, cfg.d_inner
    H = d_in // m.head_dim
    gn = m.ngroups * m.d_state
    return {
        "w_z": ParamDef((d, d_in), ("embed", "inner")),
        "w_x": ParamDef((d, d_in), ("embed", "inner")),
        "w_BC": ParamDef((d, 2 * gn), ("embed", None)),
        "w_dt": ParamDef((d, H), ("embed", "inner")),
        "conv_x": ParamDef((m.d_conv, d_in), ("conv", "inner"), scale=0.5),
        "conv_BC": ParamDef((m.d_conv, 2 * gn), ("conv", None), scale=0.5),
        "A_log": ParamDef((H,), ("inner",), init="zeros"),
        "D": ParamDef((H,), ("inner",), init="ones"),
        "dt_bias": ParamDef((H,), ("inner",), init="zeros"),
        "norm": ParamDef((d_in,), ("inner",), init="zeros"),
        "w_out": ParamDef((d_in, d), ("inner", "embed")),
    }


def _ssd_chunked(xh, dt, A, Bh, Ch, chunk, h0=None):
    """xh:(B,S,H,P) dt:(B,S,H) A:(H,) Bh,Ch:(B,S,G,N). Returns (y, h_final).

    Chunked SSD: within-chunk quadratic term via cumsum-difference decay,
    across-chunk linear recurrence via lax.scan.
    """
    B, S, H, P = xh.shape
    G, N = Bh.shape[2], Bh.shape[3]
    rep = H // G
    if S % chunk != 0:  # odd small shapes: single chunk
        chunk = S
    nc = S // chunk
    Q = chunk

    x_c = xh.reshape(B, nc, Q, H, P)
    dt_c = dt.reshape(B, nc, Q, H).astype(jnp.float32)
    B_c = jnp.repeat(Bh.reshape(B, nc, Q, G, N), rep, axis=3)  # (B,nc,Q,H,N)
    C_c = jnp.repeat(Ch.reshape(B, nc, Q, G, N), rep, axis=3)

    dA = dt_c * A.astype(jnp.float32)  # (B,nc,Q,H), <= 0
    cs = jnp.cumsum(dA, axis=2)  # inclusive
    # L[l, s] = exp(sum_{k=s+1..l} dA_k) = exp(cs_l - cs_s), l >= s.
    # Mask the *argument*, not the result: exp of the (positive, huge)
    # upper-triangle differences would overflow to inf.
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (B,nc,l,s,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -1e9))

    xdt = (x_c.astype(jnp.float32) * dt_c[..., None])  # (B,nc,Q,H,P)
    CB = jnp.einsum("bclhn,bcshn->bclsh", C_c.astype(jnp.float32), B_c.astype(jnp.float32))
    M = CB * L
    y_diag = jnp.einsum("bclsh,bcshp->bclhp", M, xdt)

    # chunk-final states: state_c = sum_s exp(cs_last - cs_s) B_s xdt_s
    decay_states = jnp.exp(cs[:, :, -1:, :] - cs)  # (B,nc,Q,H)
    states = jnp.einsum("bcshn,bcsh,bcshp->bchpn", B_c.astype(jnp.float32), decay_states, xdt)
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (B,nc,H)

    def chunk_step(h, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h  # emit state *entering* the chunk

    h_init = (
        jnp.zeros((B, H, P, N), jnp.float32)
        if h0 is None
        else h0.astype(jnp.float32)
    )
    h_final, prev_states = jax.lax.scan(
        chunk_step,
        h_init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    state_decay = jnp.exp(cs)  # (B,nc,Q,H): decay from chunk start to l
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", C_c.astype(jnp.float32), prev_states, state_decay)

    y = (y_diag + y_off).reshape(B, S, H, P)
    return y, h_final


def mamba2_apply(
    p, x, *, cfg: ModelConfig, rules, mesh, mode: str, cache: Optional[dict] = None
) -> Tuple[jnp.ndarray, dict]:
    m = cfg.mamba
    B, S, _ = x.shape
    d_in = cfg.d_inner
    H, P, G, N = d_in // m.head_dim, m.head_dim, m.ngroups, m.d_state
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    BC = jnp.einsum("bsd,de->bse", x, p["w_BC"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    xs = shard_constraint(xs, ("batch", "seq", "act_inner"), rules, mesh)

    if mode == "decode":
        assert cache is not None
        conv_dim = d_in + 2 * G * N
        col = jnp.concatenate([xs[:, 0], BC[:, 0]], -1)  # (B, conv_dim)
        win = jnp.concatenate([cache["conv"], col[:, None]], 1)  # (B,K,conv_dim)
        w_cat = jnp.concatenate([p["conv_x"], p["conv_BC"]], -1)  # (K, conv_dim)
        conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, w_cat))
        new_conv = win[:, 1:]
        xs1 = conv_out[:, :d_in].reshape(B, H, P)
        BC1 = conv_out[:, d_in:]
        B1 = BC1[:, : G * N].reshape(B, G, N)
        C1 = BC1[:, G * N:].reshape(B, G, N)
        B1 = jnp.repeat(B1, H // G, axis=1)
        C1 = jnp.repeat(C1, H // G, axis=1)
        dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        dA = jnp.exp(dt1 * A)  # (B,H)
        h = cache["ssd"].astype(jnp.float32)
        h = h * dA[..., None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dt1, B1.astype(jnp.float32), xs1.astype(jnp.float32)
        )
        y1 = jnp.einsum("bhpn,bhn->bhp", h, C1.astype(jnp.float32))
        y1 = y1 + p["D"].astype(jnp.float32)[None, :, None] * xs1.astype(jnp.float32)
        y = y1.reshape(B, 1, d_in).astype(x.dtype)
        new_cache = {"ssd": h.astype(cache["ssd"].dtype), "conv": new_conv}
    else:
        xs, conv_tail_x = causal_conv(xs, p["conv_x"])
        BC, conv_tail_bc = causal_conv(BC, p["conv_BC"])
        xs = jax.nn.silu(xs)
        BC = jax.nn.silu(BC)
        xh = xs.reshape(B, S, H, P)
        Bh = BC[..., : G * N].reshape(B, S, G, N)
        Ch = BC[..., G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        y, h_final = _ssd_chunked(xh, dt, A, Bh, Ch, min(m.chunk, S))
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(B, S, d_in).astype(x.dtype)
        conv_tail = jnp.concatenate([conv_tail_x, conv_tail_bc], -1)
        new_cache = {"ssd": h_final.astype(x.dtype), "conv": conv_tail}

    y = _gated_rmsnorm(y, z, p["norm"])
    y = shard_constraint(y, ("batch", "seq", "act_inner"), rules, mesh)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return shard_constraint(out, ("res_batch", "seq", "embed"), rules, mesh), new_cache


def mamba2_cache_defs(cfg: ModelConfig, batch: int) -> dict:
    m = cfg.mamba
    d_in = cfg.d_inner
    H = d_in // m.head_dim
    conv_dim = d_in + 2 * m.ngroups * m.d_state
    return {
        "ssd": ParamDef((batch, H, m.head_dim, m.d_state), ("batch", "inner", None, "state"), init="zeros"),
        "conv": ParamDef((batch, m.d_conv - 1, conv_dim), ("batch", None, None), init="zeros"),
    }


# ===========================================================================
# Mamba-1 (selective scan) — used by Jamba
# ===========================================================================
def mamba1_param_defs(cfg: ModelConfig) -> dict:
    m = cfg.mamba
    d, d_in, N = cfg.d_model, cfg.d_inner, m.d_state
    R = max(d // 16, 1)  # dt_rank
    return {
        "w_x": ParamDef((d, d_in), ("embed", "inner")),
        "w_z": ParamDef((d, d_in), ("embed", "inner")),
        "conv": ParamDef((m.d_conv, d_in), ("conv", "inner"), scale=0.5),
        "w_dtr": ParamDef((d_in, R), ("inner", None)),
        "w_B": ParamDef((d_in, N), ("inner", "state")),
        "w_C": ParamDef((d_in, N), ("inner", "state")),
        "dt_proj": ParamDef((R, d_in), (None, "inner")),
        "dt_bias": ParamDef((d_in,), ("inner",), init="zeros"),
        "A_log": ParamDef((d_in, N), ("inner", "state"), init="zeros"),
        "D": ParamDef((d_in,), ("inner",), init="ones"),
        "w_out": ParamDef((d_in, d), ("inner", "embed")),
    }


def _sel_scan_fused(u, dt, Bc, Cc, A, h0, chunk):
    """Fused chunked selective scan.

    u, dt: (B,S,C); Bc, Cc: (B,S,N); A: (C,N); h0: (B,C,N).
    Returns (y (B,S,C), h_final).

    The (B,S,C,N)-sized discretized operands dA/dBx are NEVER materialized
    over the full sequence: they are built per chunk inside the scan and
    contracted with C_t immediately, so the live working set is O(B·Q·C·N)
    per chunk instead of O(B·S·C·N) per layer.
    """
    B_, S, C = u.shape
    N = Bc.shape[-1]
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    Q = chunk
    u_c = u.reshape(B_, nc, Q, C).transpose(1, 0, 2, 3)
    dt_c = dt.reshape(B_, nc, Q, C).transpose(1, 0, 2, 3)
    b_cs = Bc.reshape(B_, nc, Q, N).transpose(1, 0, 2, 3)
    c_cs = Cc.reshape(B_, nc, Q, N).transpose(1, 0, 2, 3)

    def assoc(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inp):
        uq, dtq, bq, cq = inp  # (B,Q,C), (B,Q,C), (B,Q,N), (B,Q,N)
        dA = jnp.exp(dtq[..., None] * A[None, None])  # (B,Q,C,N)
        dBx = dtq[..., None] * bq[:, :, None, :] * uq[..., None]
        a_pref, b_scan = jax.lax.associative_scan(assoc, (dA, dBx), axis=1)
        h_states = a_pref * h[:, None] + b_scan  # (B,Q,C,N)
        y_q = jnp.einsum("bqcn,bqn->bqc", h_states, cq)
        return h_states[:, -1], y_q

    h_final, y = jax.lax.scan(chunk_step, h0, (u_c, dt_c, b_cs, c_cs))
    y = y.transpose(1, 0, 2, 3).reshape(B_, S, C)
    return y, h_final


def mamba1_apply(
    p, x, *, cfg: ModelConfig, rules, mesh, mode: str, cache: Optional[dict] = None
) -> Tuple[jnp.ndarray, dict]:
    m = cfg.mamba
    B, S, _ = x.shape
    d_in, N = cfg.d_inner, m.d_state
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (C,N)

    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xs = shard_constraint(xs, ("batch", "seq", "act_inner"), rules, mesh)

    if mode == "decode":
        assert cache is not None
        win = jnp.concatenate([cache["conv"], xs[:, 0][:, None]], 1)
        conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, p["conv"]))
        new_conv = win[:, 1:]
        u = conv_out  # (B,C)
        dtr = jnp.einsum("bc,cr->br", u, p["w_dtr"])
        dt = jax.nn.softplus(
            jnp.einsum("br,rc->bc", dtr, p["dt_proj"]).astype(jnp.float32)
            + p["dt_bias"].astype(jnp.float32)
        )
        Bc = jnp.einsum("bc,cn->bn", u, p["w_B"]).astype(jnp.float32)
        Cc = jnp.einsum("bc,cn->bn", u, p["w_C"]).astype(jnp.float32)
        dA = jnp.exp(dt[..., None] * A)  # (B,C,N)
        dBx = dt[..., None] * Bc[:, None, :] * u.astype(jnp.float32)[..., None]
        h = cache["h"].astype(jnp.float32) * dA + dBx
        y1 = jnp.einsum("bcn,bn->bc", h, Cc) + p["D"].astype(jnp.float32) * u.astype(jnp.float32)
        y = y1[:, None].astype(x.dtype)
        new_cache = {"h": h.astype(cache["h"].dtype), "conv": new_conv}
    else:
        u, conv_tail = causal_conv(xs, p["conv"])
        u = jax.nn.silu(u)
        dtr = jnp.einsum("bse,er->bsr", u, p["w_dtr"])
        dt = jax.nn.softplus(
            jnp.einsum("bsr,rc->bsc", dtr, p["dt_proj"]).astype(jnp.float32)
            + p["dt_bias"].astype(jnp.float32)
        )
        Bc = jnp.einsum("bse,en->bsn", u, p["w_B"]).astype(jnp.float32)
        Cc = jnp.einsum("bse,en->bsn", u, p["w_C"]).astype(jnp.float32)
        uf = u.astype(jnp.float32)
        h0 = jnp.zeros((B, d_in, N), jnp.float32)
        y, h_final = _sel_scan_fused(uf, dt, Bc, Cc, A, h0, min(m.chunk, S))
        y = (y + p["D"].astype(jnp.float32) * uf).astype(x.dtype)
        new_cache = {"h": h_final.astype(x.dtype), "conv": conv_tail}

    y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return shard_constraint(out, ("res_batch", "seq", "embed"), rules, mesh), new_cache


def mamba1_cache_defs(cfg: ModelConfig, batch: int) -> dict:
    m = cfg.mamba
    return {
        "h": ParamDef((batch, cfg.d_inner, m.d_state), ("batch", "inner", "state"), init="zeros"),
        "conv": ParamDef((batch, m.d_conv - 1, cfg.d_inner), ("batch", None, "inner"), init="zeros"),
    }
