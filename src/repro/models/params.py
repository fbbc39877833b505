"""Minimal functional parameter system (no flax dependency).

A model is described by a pytree of ``ParamDef`` leaves; materialization,
sharding and AOT stand-ins (ShapeDtypeStructs for the dry-run) all derive
from the same tree, so the compiled artifact and the runtime can never
disagree about shapes or logical axes.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import ShardingRules, pspec_for, sharding_for


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones
    scale: Optional[float] = None  # stddev; None => 1/sqrt(fan_in) (dim 0)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(f, defs):
    return jax.tree_util.tree_map(f, defs, is_leaf=is_def)


def _default_scale(shape) -> float:
    """1/sqrt(fan_in), with dim 0 as the fan-in (the only dim of a vector)."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    return 1.0 / np.sqrt(max(fan_in, 1))


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, *, shape, dtype):
    # one fused program: as separate eager ops, each float32 intermediate of
    # the whole leaf stays allocated until the device reaches it, and a host
    # that runs ahead of the device piles them up (11.6 GB on a TPU v5e
    # chip for h2o-danube-1.8b in bf16, whose weights are 3.66 GB)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _materialize(d: ParamDef, key, dtype):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    scale = d.scale if d.scale is not None else _default_scale(d.shape)
    return _normal(key, float(scale), shape=d.shape, dtype=dtype)


def init_params(defs, key, dtype=jnp.float32):
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_materialize(d, k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def param_shardings(defs, rules: ShardingRules, mesh):
    return tree_map_defs(lambda d: sharding_for(d.axes, rules, mesh), defs)


def param_pspecs(defs, rules: ShardingRules, mesh):
    return tree_map_defs(lambda d: pspec_for(d.axes, rules, mesh), defs)


def param_shape_structs(defs, dtype, rules: Optional[ShardingRules] = None, mesh=None):
    """ShapeDtypeStruct stand-ins (with shardings if a mesh is given) — the
    dry-run path: no device allocation ever happens."""

    def mk(d: ParamDef):
        sh = sharding_for(d.axes, rules, mesh) if rules is not None else None
        return jax.ShapeDtypeStruct(d.shape, dtype, sharding=sh)

    return tree_map_defs(mk, defs)


def count_params(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_def)
    return int(sum(int(np.prod(d.shape)) for d in leaves))


def stack_defs(defs, n: int, axis_name: str = "periods"):
    """Prefix every leaf with a leading stacking dim (for lax.scan layers).
    The init scale stays that of one layer's weight: the stacking dim is
    not a fan-in."""
    return tree_map_defs(
        lambda d: ParamDef(
            (n,) + d.shape, (axis_name,) + d.axes, d.init,
            d.scale if d.scale is not None else _default_scale(d.shape),
        ),
        defs,
    )
