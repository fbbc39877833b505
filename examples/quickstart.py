"""Quickstart: build a model, run a prefill and one decode step.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import forward, init_cache_defs, model_param_defs
from repro.models.model import logits_for
from repro.models.params import count_params, init_params
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config


def main() -> None:
    # Any assigned architecture works; on CPU we use the reduced
    # same-family config.
    cfg = reduced(get_config("gemma2-2b"))
    ec = make_exec_config(cfg, tp=1)
    defs = model_param_defs(cfg, ec)
    params = init_params(defs, jax.random.PRNGKey(0), jnp.float32)
    print(f"model: {cfg.name} ({count_params(defs)/1e6:.2f} M params, "
          f"pattern={[t.mixer for t in cfg.layer_pattern]})")

    B, S = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    # prefill + one decode step
    h, cache = forward(params, cfg, ec, rules=DEFAULT_RULES, mesh=None,
                       tokens=tokens, mode="prefill", block_q=16, block_k=16)
    logits = logits_for(params, cfg, h[:, -1:], DEFAULT_RULES, None)
    nxt = jnp.argmax(logits[:, 0, : cfg.vocab_size], -1)
    print("prefill ok; first sampled tokens:", np.asarray(nxt))

    # the decode step reads caches of its own capacity: copy the prefill's
    # into zero caches one token longer (a windowed layer keeps its window)
    room = init_params(init_cache_defs(cfg, ec, B, S + 1), jax.random.PRNGKey(2), jnp.float32)
    cache = jax.tree_util.tree_map(
        lambda c, p: c.at[:, :, :, : p.shape[3]].set(p) if c.ndim == 5 else p, room, cache
    )
    h, cache = forward(params, cfg, ec, rules=DEFAULT_RULES, mesh=None,
                       tokens=nxt[:, None].astype(jnp.int32),
                       positions=jnp.full((B,), S, jnp.int32),
                       cache=cache, mode="decode")
    print("decode ok; hidden:", h.shape)
    print("quickstart done")


if __name__ == "__main__":
    main()
