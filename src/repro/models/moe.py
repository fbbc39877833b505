"""Top-k MoE with expert parallelism over the model axis.

Three execution paths:
  * local   — no mesh (CPU smoke tests): sort-based capacity dispatch, all
              experts resident.
  * sharded — prefill under a mesh: tokens are flattened over
              (data x model) inside a shard_map, dispatched locally
              (sort-based), then moved to their expert shards with an
              all_to_all over the model axis, expert-GEMMed, and moved back.
  * decode  — tiny token counts: dispatch is replicated across the model
              axis, each column computes only its local experts, outputs are
              psum-combined. No all_to_all; communication is O(tokens·d).

All paths share the same routing/dispatch math, so unit tests can assert the
sharded paths agree with the local oracle.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoESpec
from repro.models.params import ParamDef
from repro.parallel.sharding import pspec_for, shard_constraint


def _expert_weight_specs(rules, mesh):
    """(w_gate/w_in spec, w_out spec, fsdp-gather axes or None).

    With `expert_embed -> data` the expert weights are additionally sharded
    over the data axis (expert-weight FSDP, needed when per-chip expert
    shards exceed HBM, e.g. dbrx); they are all-gathered just-in-time inside
    the shard_map body.
    """
    wg = pspec_for(("experts", "expert_embed", "expert_mlp"), rules, mesh)
    wo = pspec_for(("experts", "expert_mlp", "expert_embed"), rules, mesh)
    ax = rules.get("expert_embed")
    if ax is not None:
        flat = (ax,) if isinstance(ax, str) else tuple(ax)
        ax = tuple(a for a in flat if a in mesh.axis_names) or None
    return wg, wo, ax


def _gather_weights(w_gate, w_in, w_out, fsdp_axes):
    if fsdp_axes is None:
        return w_gate, w_in, w_out
    w_gate = jax.lax.all_gather(w_gate, fsdp_axes, axis=1, tiled=True)
    w_in = jax.lax.all_gather(w_in, fsdp_axes, axis=1, tiled=True)
    w_out = jax.lax.all_gather(w_out, fsdp_axes, axis=2, tiled=True)
    return w_gate, w_in, w_out


def moe_param_defs(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.02),
        "w_gate": ParamDef((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_in": ParamDef((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_out": ParamDef((e, f, d), ("experts", "expert_mlp", "expert_embed")),
    }
    if m.num_shared_experts:
        fs = m.num_shared_experts * f
        defs["shared"] = {
            "w_gate": ParamDef((d, fs), ("embed", "mlp")),
            "w_in": ParamDef((d, fs), ("embed", "mlp")),
            "w_out": ParamDef((fs, d), ("mlp", "embed")),
        }
    return defs


def _route(x2d, router_w, m: MoESpec):
    """x2d: (T,D) -> (probs (T,K), idx (T,K))."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w.astype(jnp.float32))
    probs_all = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs_all, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i


def _dispatch_indices(top_i, E: int, C: int):
    """Sort-based capacity dispatch.

    Returns (dest (T*K,), tok (T*K,), keep (T*K,)): assignment a goes to
    dispatch row `dest[a]` (within (E*C)) from token `tok[a]`; dropped
    assignments (over capacity) have keep=False and dest pointing at a trash
    row E*C.
    """
    TK = top_i.size
    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(TK) - first
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    tok = order // top_i.shape[1]
    return dest, tok, keep, order


def _expert_ffn(buf, w_gate, w_in, w_out):
    """buf: (E,C,D); weights: (E,D,F)/(E,F,D) -> (E,C,D)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_in)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_out)


def _capacity(T: int, m: MoESpec, floor: int = 8) -> int:
    c = math.ceil(T * m.top_k / m.num_experts * m.capacity_factor)
    return max(int(c), floor)


def _moe_core(x2d, p, m: MoESpec, C: int):
    """Shared dispatch->ffn->combine on local tokens, all experts local."""
    T, D = x2d.shape
    E = m.num_experts
    top_p, top_i = _route(x2d, p["router"], m)
    dest, tok, keep, order = _dispatch_indices(top_i, E, C)
    buf = jnp.zeros((E * C + 1, D), x2d.dtype).at[dest].set(x2d[tok])
    out = _expert_ffn(buf[:-1].reshape(E, C, D), p["w_gate"], p["w_in"], p["w_out"])
    out_rows = out.reshape(E * C, D)
    gathered = jnp.where(keep[:, None], out_rows[jnp.minimum(dest, E * C - 1)], 0.0)
    w = top_p.reshape(-1)[order][:, None].astype(x2d.dtype)
    return jnp.zeros((T, D), x2d.dtype).at[tok].add(gathered * w)


def moe_apply_local(p, x, cfg: ModelConfig, rules=None, mesh=None):
    m = cfg.moe
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    y = _moe_core(x2d, p, m, _capacity(x2d.shape[0], m)).reshape(B, S, D)
    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x, rules, mesh)
    return y


def _shared_ffn(ps, x, rules, mesh):
    g = jnp.einsum("bsd,df->bsf", x, ps["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, ps["w_in"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, ps["w_out"])


# ---------------------------------------------------------------------------
# Sharded prefill path: tokens flattened over (data x model), EP via
# all_to_all over 'model'.
# ---------------------------------------------------------------------------
def moe_apply_sharded(p, x, cfg: ModelConfig, rules, mesh):
    m = cfg.moe
    B, S, D = x.shape
    E = m.num_experts
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    tp = mesh.shape["model"]
    E_loc = E // tp

    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    if S % tp != 0 or B % dp != 0:
        # decode / tiny shapes: replicated dispatch + psum combine
        return _moe_apply_decode(p, x, cfg, rules, mesh)

    T_loc = (B // dp) * (S // tp)
    C_loc = _capacity(T_loc, m)

    wg_spec, wo_spec, fsdp_axes = _expert_weight_specs(rules, mesh)

    def inner(x_loc, router_w, w_gate, w_in, w_out):
        Bl, Sl, _ = x_loc.shape
        w_gate, w_in, w_out = _gather_weights(w_gate, w_in, w_out, fsdp_axes)
        x2d = x_loc.reshape(-1, D)
        top_p, top_i = _route(x2d, router_w, m)
        dest, tok, keep, order = _dispatch_indices(top_i, E, C_loc)
        buf = jnp.zeros((E * C_loc + 1, D), x2d.dtype).at[dest].set(x2d[tok])
        buf = buf[:-1].reshape(E, C_loc, D)
        # -> expert shards: (E, C_loc, D) -> (E_loc, C_loc*tp, D)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(buf, w_gate, w_in, w_out)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0, tiled=True)
        out_rows = out.reshape(E * C_loc, D)
        gathered = jnp.where(keep[:, None], out_rows[jnp.minimum(dest, E * C_loc - 1)], 0.0)
        w = top_p.reshape(-1)[order][:, None].astype(x2d.dtype)
        y = jnp.zeros_like(x2d).at[tok].add(gathered * w)
        return y.reshape(Bl, Sl, D)

    xspec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None), "model", None)
    y = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wg_spec, wg_spec, wo_spec),
        out_specs=xspec,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    y = shard_constraint(y, ("res_batch", "seq", "embed"), rules, mesh)
    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x, rules, mesh)
    return y


def _moe_apply_decode(p, x, cfg: ModelConfig, rules, mesh):
    """Replicated dispatch + local-expert compute + psum over model."""
    m = cfg.moe
    B, S, D = x.shape
    E = m.num_experts
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    tp = mesh.shape["model"]
    E_loc = E // tp
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    B_loc = B // dp if B % dp == 0 else B
    T_loc = B_loc * S
    C = _capacity(T_loc, m)

    wg_spec, wo_spec, fsdp_axes = _expert_weight_specs(rules, mesh)

    def inner(x_loc, router_w, w_gate, w_in, w_out):
        Bl, Sl, _ = x_loc.shape
        w_gate, w_in, w_out = _gather_weights(w_gate, w_in, w_out, fsdp_axes)
        x2d = x_loc.reshape(-1, D)
        top_p, top_i = _route(x2d, router_w, m)
        dest, tok, keep, order = _dispatch_indices(top_i, E, C)
        buf = jnp.zeros((E * C + 1, D), x2d.dtype).at[dest].set(x2d[tok])
        buf = buf[:-1].reshape(E, C, D)
        col = jax.lax.axis_index("model")
        my = jax.lax.dynamic_slice_in_dim(buf, col * E_loc, E_loc, axis=0)
        out_loc = _expert_ffn(my, w_gate, w_in, w_out)  # (E_loc, C, D)
        out = jnp.zeros((E, C, D), x2d.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, out_loc, col * E_loc, axis=0)
        out_rows = out.reshape(E * C, D)
        gathered = jnp.where(keep[:, None], out_rows[jnp.minimum(dest, E * C - 1)], 0.0)
        w = top_p.reshape(-1)[order][:, None].astype(x2d.dtype)
        y = jnp.zeros_like(x2d).at[tok].add(gathered * w)
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, Sl, D)

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    xspec = P(bspec if B % dp == 0 and dp > 1 else None, None, None)
    y = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wg_spec, wg_spec, wo_spec),
        out_specs=xspec,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    y = shard_constraint(y, ("res_batch", "seq", "embed"), rules, mesh)
    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x, rules, mesh)
    return y


def moe_apply(p, x, cfg: ModelConfig, rules, mesh):
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return moe_apply_local(p, x, cfg, rules, mesh)
    return moe_apply_sharded(p, x, cfg, rules, mesh)
