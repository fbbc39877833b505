"""Model stack builder: dense / MoE / SSM / hybrid decoder assembly.

The layer stack is a ``lax.scan`` over *pattern periods* (HLO size stays
O(period) even for 88-layer models), with ``jax.checkpoint`` remat around the
period body in training. The same ``forward`` serves train, prefill and
decode; caches thread through the scan as xs/ys.
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
    tokens=None,
    embeds=None,
    positions=None,
    cache=None,
    mode: str = "train",
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, Optional[dict], dict]:
    """Returns (hidden (B,S,D) post-final-norm, new_cache, aux)."""
    assert mode in ("train", "prefill", "decode")
    if embeds is None:
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

    def apply_layer(h, aux, lp, lc, t):
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
                block_q=block_q,
                block_k=block_k,
            )
        else:
            fn = mamba2_apply if cfg.mamba.version == 2 else mamba1_apply
            y, nc = fn(lp["mixer"], hn, cfg=cfg, rules=rules, mesh=mesh, mode=mode, cache=lc)
        h = resid + y
        if t.ffn != "none":
            resid = h
            hn = rmsnorm(h, lp["norm2"], cfg.norm_eps)
            if t.ffn == "dense":
                y = mlp_apply(lp["ffn"], hn, rules, mesh)
            else:
                y, a = moe_apply(lp["ffn"], hn, cfg, rules, mesh)
                aux = {k: aux[k] + a[k] for k in aux}
            h = resid + y
        return h, aux, nc

    # two-level remat for multi-layer periods (jamba's 8-layer block):
    # the period scan saves only period boundaries; per-layer checkpointing
    # bounds the recompute working set to ONE layer's intermediates instead
    # of the whole period's (§Perf, jamba train iteration)
    if mode == "train" and len(pattern) > 1:
        apply_layer = jax.checkpoint(apply_layer, static_argnums=(4,))

    def body(carry, xs):
        h, aux = carry
        pparams, pcache = xs
        new_pcache = {}
        for i, t in enumerate(pattern):
            lp = pparams[f"pos{i}"]
            lc = pcache.get(f"pos{i}") if pcache else None
            h, aux, nc = apply_layer(h, aux, lp, lc, t)
            if nc is not None:
                new_pcache[f"pos{i}"] = nc
            if mode == "train" and len(pattern) > 1:
                # barrier between in-period layers: stops the scheduler from
                # hoisting every layer's remat-recompute ahead of the layer
                # backwards (which would keep all layers' intermediates live)
                h, aux = jax.lax.optimization_barrier((h, aux))
        # residual stream at the period boundary: this is what remat saves
        # per scan step — sequence-parallel under training rules
        h = shard_constraint(h, ("res_batch", "seq_res", "embed"), rules, mesh)
        return (h, aux), new_pcache

    if mode == "train":
        body = jax.checkpoint(body)

    aux0 = {"lb": jnp.zeros((), jnp.float32), "z": jnp.zeros((), jnp.float32)}
    xs = (params["periods"], cache if cache is not None else {})
    (h, aux), new_cache = jax.lax.scan(body, (h, aux0), xs)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if mode == "train":
        new_cache = None
    return h, new_cache, aux


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


def loss_fn(
    params,
    cfg: ModelConfig,
    ec: ExecConfig,
    batch: dict,
    *,
    rules,
    mesh,
    seq_chunk: int = 512,
    block_q: int = 512,
    block_k: int = 512,
):
    """Chunked cross-entropy train loss (full logits never materialized)."""
    tokens = batch["tokens"]
    targets = batch["targets"]
    mask = batch.get("mask")
    B, S = tokens.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    embeds = batch.get("embeds")
    h, _, aux = forward(
        params, cfg, ec, rules=rules, mesh=mesh, tokens=tokens, embeds=embeds,
        mode="train", block_q=block_q, block_k=block_k,
    )
    w = _head_matrix(params, cfg)
    ck = min(seq_chunk, S)
    nc = S // ck
    h_c = h.reshape(B, nc, ck, -1).transpose(1, 0, 2, 3)
    t_c = targets.reshape(B, nc, ck).transpose(1, 0, 2)
    m_c = mask.reshape(B, nc, ck).transpose(1, 0, 2)

    def ce_chunk(tot, xs):
        hc, tc, mc = xs
        logits = jnp.einsum("bsd,dv->bsv", hc, w, preferred_element_type=jnp.float32)
        logits = softcap(logits, cfg.final_logit_softcap)
        logits = shard_constraint(logits, ("batch", "seq", "vocab"), rules, mesh)
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return tot + ((lse - tgt) * mc).sum(), None

    tot, _ = jax.lax.scan(ce_chunk, jnp.zeros((), jnp.float32), (h_c, t_c, m_c))
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = tot / denom
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux["lb"] / cfg.num_periods
        loss = loss + cfg.moe.router_z_weight * aux["z"] / cfg.num_periods
    return loss, {"ce": tot / denom, **aux}
