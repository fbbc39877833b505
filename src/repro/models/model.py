"""Model stack builder: dense / MoE / SSM / hybrid decoder assembly.

The layer stack is a ``lax.scan`` over *pattern periods* (HLO size stays
O(period) even for 88-layer models). ``forward`` serves prefill and decode.
Prefill returns a fresh cache per period as the scan's ys. Decode carries the
stacked caches through the scan: each layer writes its new rows in place and
reads its own slice.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import attn_apply, attn_cache_defs, attn_param_defs
from repro.models.layers import mlp_apply, mlp_param_defs, norm_def, rmsnorm, softcap
from repro.models.mamba import (
    mamba1_apply,
    mamba1_cache_defs,
    mamba1_param_defs,
    mamba2_apply,
    mamba2_cache_defs,
    mamba2_param_defs,
)
from repro.models.moe import moe_apply, moe_param_defs
from repro.models.params import ParamDef, stack_defs
from repro.parallel.sharding import ExecConfig, shard_constraint


def _layer_window(cfg: ModelConfig, mixer: str) -> Optional[int]:
    if mixer == "attn_local" or (mixer == "attn" and cfg.attn.kind == "swa"):
        return cfg.attn.window
    return None


def model_param_defs(cfg: ModelConfig, ec: ExecConfig) -> dict:
    d = cfg.d_model
    per_period = {}
    for i, t in enumerate(cfg.layer_pattern):
        layer = {"norm1": norm_def(d)}
        if t.mixer.startswith("attn"):
            layer["mixer"] = attn_param_defs(cfg, ec)
        elif t.mixer == "mamba":
            layer["mixer"] = (
                mamba2_param_defs(cfg) if cfg.mamba.version == 2 else mamba1_param_defs(cfg)
            )
        else:
            raise ValueError(t.mixer)
        if t.ffn == "dense":
            layer["norm2"] = norm_def(d)
            layer["ffn"] = mlp_param_defs(d, cfg.d_ff)
        elif t.ffn == "moe":
            layer["norm2"] = norm_def(d)
            layer["ffn"] = moe_param_defs(cfg)
        per_period[f"pos{i}"] = layer

    defs = {
        "embed": ParamDef((cfg.vocab_padded, d), ("vocab", "embed"), scale=1.0),
        "periods": stack_defs(per_period, cfg.num_periods),
        "final_norm": norm_def(d),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, cfg.vocab_padded), ("embed", "vocab"))
    return defs


def init_cache_defs(cfg: ModelConfig, ec: ExecConfig, batch: int, seq_len: int) -> dict:
    """Cache ParamDefs, stacked over periods, keyed by in-period position."""
    out = {}
    for i, t in enumerate(cfg.layer_pattern):
        if t.mixer.startswith("attn"):
            window = _layer_window(cfg, t.mixer)
            c = attn_cache_defs(cfg, ec, batch, seq_len, window)
        elif t.mixer == "mamba":
            c = (
                mamba2_cache_defs(cfg, batch)
                if cfg.mamba.version == 2
                else mamba1_cache_defs(cfg, batch)
            )
        out[f"pos{i}"] = c
    return stack_defs(out, cfg.num_periods)


def forward(
    params,
    cfg: ModelConfig,
    ec: ExecConfig,
    *,
    rules,
    mesh,
    mode: str,
    tokens=None,
    embeds=None,
    positions=None,
    cache=None,
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, dict]:
    """Returns (hidden (B,S,D) post-final-norm, cache). `mode` is "prefill"
    (a fresh cache of the S tokens) or "decode" (reads and returns `cache`,
    the stacked caches of `init_cache_defs`)."""
    if mode not in ("prefill", "decode"):
        raise ValueError(f"forward: mode {mode!r} is neither 'prefill' nor 'decode'")
    if embeds is None:
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0)
            if cfg.tie_embeddings:  # gemma convention: scale tied embeddings
                h = h * jnp.asarray(cfg.d_model**0.5, h.dtype)
    else:
        h = embeds
    B, S = h.shape[0], h.shape[1]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    h = shard_constraint(h, ("res_batch", "seq", "embed"), rules, mesh)
    pattern = cfg.layer_pattern

    def apply_layer(h, lp, lc, t, layer=None):
        # named scopes ("attention", "mlp") cover each block's norm and residual;
        # in decode `lc` is stacked over periods and `layer` indexes it
        with jax.named_scope("attention" if t.mixer.startswith("attn") else t.mixer):
            resid = h
            hn = rmsnorm(h, lp["norm1"], cfg.norm_eps)
            if t.mixer.startswith("attn"):
                y, nc = attn_apply(
                    lp["mixer"],
                    hn,
                    cfg=cfg,
                    ec=ec,
                    rules=rules,
                    mesh=mesh,
                    positions=positions,
                    window=_layer_window(cfg, t.mixer),
                    mode=mode,
                    cache=lc,
                    layer=layer,
                    block_q=block_q,
                    block_k=block_k,
                )
            else:
                fn = mamba2_apply if cfg.mamba.version == 2 else mamba1_apply
                tm = jax.tree_util.tree_map
                sl = lc if layer is None else tm(
                    lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False), lc
                )
                y, nc = fn(lp["mixer"], hn, cfg=cfg, rules=rules, mesh=mesh, mode=mode, cache=sl)
                if layer is not None:  # the state is small: write the slice back whole
                    nc = tm(lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, layer, 0), lc, nc)
            h = resid + y
        if t.ffn != "none":
            with jax.named_scope("mlp" if t.ffn == "dense" else t.ffn):
                resid = h
                hn = rmsnorm(h, lp["norm2"], cfg.norm_eps)
                if t.ffn == "dense":
                    y = mlp_apply(lp["ffn"], hn, rules, mesh)
                else:
                    y = moe_apply(lp["ffn"], hn, cfg, rules, mesh)
                h = resid + y
        return h, nc

    def body(carry, xs):
        h, stacked = carry  # stacked: decode's caches, else empty
        pparams, layer = xs  # layer: decode's index into them
        stacked, new_pcache = dict(stacked), {}
        for i, t in enumerate(pattern):
            key = f"pos{i}"
            if mode == "decode":
                h, stacked[key] = apply_layer(h, pparams[key], stacked[key], t, layer)
            else:
                h, new_pcache[key] = apply_layer(h, pparams[key], None, t)
        h = shard_constraint(h, ("res_batch", "seq", "embed"), rules, mesh)
        return (h, stacked), new_pcache

    if mode == "decode":
        # the stacked caches ride in the carry, so each layer writes its new
        # rows into the donated buffers in place: as xs/ys, each layer's slice
        # would be copied out and back, and the whole stack once more after
        carry = (h, dict(cache))
        layers = jnp.arange(cfg.num_periods, dtype=jnp.int32)
    else:
        carry, layers = (h, {}), None
    (h, stacked), new_cache = jax.lax.scan(body, carry, (params["periods"], layers))
    if mode == "decode":
        new_cache = stacked
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return h, new_cache


def _head_matrix(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def logits_for(params, cfg: ModelConfig, h, rules, mesh):
    """h: (B,S,D) -> logits (B,S,V) f32 (+ final softcap)."""
    w = _head_matrix(params, cfg)
    logits = jnp.einsum("bsd,dv->bsv", h, w, preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_logit_softcap)
    return shard_constraint(logits, ("batch", "seq", "vocab"), rules, mesh)

