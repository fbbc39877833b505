"""Per-architecture smoke tests (reduced configs) + prefill/decode consistency.

Every assigned architecture instantiates a reduced same-family config, runs a
prefill forward and the head on CPU, and asserts output shapes + finiteness.
The decode-consistency test is the core serving invariant: prefill(S)
followed by decode(S) must match a prefill over S+1 tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.models import forward, init_params, model_param_defs, init_cache_defs
from repro.models.model import logits_for
from repro.models.params import init_params as init_p, param_shape_structs
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config

RULES = DEFAULT_RULES


def _setup(name, dtype=jnp.float32, **overrides):
    cfg = dataclasses.replace(reduced(get_config(name)), **overrides)
    ec = make_exec_config(cfg, tp=1)
    defs = model_param_defs(cfg, ec)
    params = init_p(defs, jax.random.PRNGKey(0), dtype)
    return cfg, ec, params


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_smoke_prefill_forward_and_logits(name):
    cfg, ec, params = _setup(name)
    B, S = 2, 32
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    embeds = None
    if cfg.frontend == "encodec":  # stub frontend: precomputed frame embeds
        embeds = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32) * 0.02

    h, _ = forward(
        params, cfg, ec, rules=RULES, mesh=None,
        tokens=tokens, embeds=embeds, mode="prefill",
        block_q=16, block_k=16,
    )
    assert h.shape == (B, S, cfg.d_model)
    assert bool(jnp.isfinite(h).all()), f"{name}: non-finite hidden states"

    logits = logits_for(params, cfg, h, RULES, None)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all())


def test_forward_serves_only_prefill_and_decode():
    cfg, ec, params = _setup("h2o-danube-1.8b")
    tokens = jnp.zeros((1, 8), jnp.int32)
    removed = "train"  # the mode of a training stack this model no longer has
    with pytest.raises(ValueError, match="'train' is neither 'prefill' nor 'decode'"):
        forward(params, cfg, ec, rules=RULES, mesh=None, tokens=tokens, mode=removed)
    with pytest.raises(TypeError, match="mode"):
        forward(params, cfg, ec, rules=RULES, mesh=None, tokens=tokens)


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_prefill_decode_matches_full_forward(name):
    cfg, ec, params = _setup(name)
    B, S = 2, 24
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    embeds = None
    if cfg.frontend == "encodec":
        embeds = jax.random.normal(key, (B, S + 1, cfg.d_model), jnp.float32) * 0.02

    # ground truth: a prefill over S+1 tokens
    h_full, _ = forward(
        params, cfg, ec, rules=RULES, mesh=None,
        tokens=tokens, embeds=embeds, mode="prefill", block_q=8, block_k=8,
    )

    # prefill S tokens, then decode token S
    h_pre, cache = forward(
        params, cfg, ec, rules=RULES, mesh=None,
        tokens=tokens[:, :S], embeds=None if embeds is None else embeds[:, :S],
        mode="prefill", block_q=8, block_k=8,
    )
    np.testing.assert_allclose(
        np.asarray(h_pre), np.asarray(h_full[:, :S]), rtol=2e-4, atol=2e-4
    )

    # grow attention caches from prefill length S to S+1 capacity
    cache_big = _grow_cache(cfg, cache, extra=8)
    positions = jnp.full((B,), S, jnp.int32)
    h_dec, _ = forward(
        params, cfg, ec, rules=RULES, mesh=None,
        tokens=tokens[:, S:S + 1],
        embeds=None if embeds is None else embeds[:, S:S + 1],
        positions=positions, cache=cache_big, mode="decode",
    )
    np.testing.assert_allclose(
        np.asarray(h_dec[:, 0]), np.asarray(h_full[:, S]), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize(
    "name,head_dim",
    [(n, None) for n in ASSIGNED_ARCHS] + [("yi-34b", 128), ("h2o-danube-1.8b", 128)],
)
def test_decode_writes_one_row_per_layer_in_place(name, head_dim):
    """Decode returns the caches it was given with one row per layer and slot
    changed, at the slot's position (its rotating slot in a windowed layer),
    and that row is what a prefill of one more token writes. A Mamba state is
    rewritten whole and equals that prefill's state. Heads of 128 fill the
    TPU's lanes, and take the scatter in place of the row-by-row write."""
    cfg, ec, params = _setup(name, **({} if head_dim is None else {"head_dim": head_dim}))
    pos = (7, 19)  # one per slot; 19 wraps a reduced window of 16
    B, Sc = len(pos), 24
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(key, (B, max(pos) + 1), 0, cfg.vocab_size)
    embeds = None
    if cfg.frontend == "encodec":
        embeds = jax.random.normal(key, (B, max(pos) + 1, cfg.d_model), jnp.float32) * 0.02

    def prefill(b, n):
        _, c = forward(
            params, cfg, ec, rules=RULES, mesh=None, tokens=tokens[b:b + 1, :n],
            embeds=None if embeds is None else embeds[b:b + 1, :n],
            mode="prefill", block_q=8, block_k=8,
        )
        return c

    # noise everywhere, each slot's prefix cache (or state) over it; every
    # leaf is (L, B, ...), and a K/V cache (L, B, KV, Sc, hd)
    defs = init_cache_defs(cfg, ec, B, Sc)
    leaves, tree = jax.tree_util.tree_flatten(defs, is_leaf=lambda d: hasattr(d, "axes"))
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    cache = tree.unflatten([jax.random.normal(k, d.shape) for k, d in zip(keys, leaves)])
    expect = cache
    for b, p in enumerate(pos):
        cache = jax.tree_util.tree_map(
            lambda c, s: c.at[:, b, :, : s.shape[3]].set(s[:, 0]), cache, prefill(b, p))
        expect = jax.tree_util.tree_map(
            lambda c, s: c.at[:, b, :, : s.shape[3]].set(s[:, 0]), expect, prefill(b, p + 1))

    last = jnp.stack([tokens[b, p] for b, p in enumerate(pos)])[:, None]
    _, out = forward(
        params, cfg, ec, rules=RULES, mesh=None, tokens=last,
        embeds=None if embeds is None else jnp.stack(
            [embeds[b, p] for b, p in enumerate(pos)])[:, None],
        positions=jnp.asarray(pos, jnp.int32), cache=cache, mode="decode",
    )
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(cache)
    for key_, c in cache.items():
        for n, before in c.items():
            after, want = np.asarray(out[key_][n]), np.asarray(expect[key_][n])
            if n not in ("k", "v"):  # a Mamba state
                np.testing.assert_allclose(after, want, rtol=2e-4, atol=2e-4)
                continue
            row = np.zeros(after.shape[:4], bool)  # (L, B, KV, Sc)
            for b, p in enumerate(pos):
                row[:, b, :, p % after.shape[3]] = True
            np.testing.assert_array_equal(after[~row], np.asarray(before)[~row])
            np.testing.assert_allclose(after[row], want[row], rtol=2e-4, atol=2e-4)


def _grow_cache(cfg, cache, extra: int):
    """Pad attention KV caches (L, B, KV, S, hd) with `extra` free slots
    (windowed caches are rotating buffers and never grow)."""
    out = {}
    for pos, c in cache.items():
        if "k" in c:  # attention
            i = int(pos[3:])
            t = cfg.layer_pattern[i]
            window = cfg.attn.window if (
                t.mixer == "attn_local" or (t.mixer == "attn" and cfg.attn.kind == "swa")
            ) else None
            if window is not None and c["k"].shape[3] >= window:
                out[pos] = c
            else:
                pad = [(0, 0), (0, 0), (0, 0), (0, extra), (0, 0)]
                out[pos] = {k: jnp.pad(v, pad) for k, v in c.items()}
        else:
            out[pos] = c
    return out


def test_gemma2_softcap_and_tied_head():
    cfg, ec, params = _setup("gemma2-2b")
    assert cfg.tie_embeddings and "lm_head" not in params
    B, S = 1, 16
    tokens = jnp.zeros((B, S), jnp.int32)
    h, _ = forward(params, cfg, ec, rules=RULES, mesh=None, tokens=tokens,
                   mode="prefill", block_q=8, block_k=8)
    logits = logits_for(params, cfg, h, RULES, None)
    assert float(jnp.abs(logits).max()) <= cfg.final_logit_softcap + 1e-3


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_param_counts_match_config_estimate(name):
    from repro.models.params import count_params
    cfg = get_config(name)
    ec = make_exec_config(cfg, tp=1)
    defs = model_param_defs(cfg, ec)
    actual = count_params(defs)
    est = cfg.param_count()
    # estimate ignores small per-layer extras (qk-norm scales, dt params);
    # must agree within 2%
    assert abs(actual - est) / est < 0.02, (name, actual, est)
