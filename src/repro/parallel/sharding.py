"""Logical-axis sharding rules (MaxText-style) + TP execution planning.

Tensors carry *logical* axis names; a ``ShardingRules`` table maps each
logical axis to zero or more mesh axes. Changing the distribution strategy
(the hillclimb lever) means swapping rule tables, not touching model code.

``ExecConfig`` resolves an architecture against a TP degree: query heads are
padded up and KV heads block-replicated when the TP degree exceeds the head
counts (vLLM-style), so every assigned arch shards on the 16-wide model axis.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ceil_to

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    table: Mapping[str, MeshAxes]

    def get(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        if logical not in self.table:
            raise KeyError(f"unknown logical axis {logical!r}")
        return self.table[logical]


DEFAULT_RULES = ShardingRules(
    {
        # activations
        "batch": ("pod", "data"),
        # residual-stream batch: resolves as "batch" does under these rules
        "res_batch": ("pod", "data"),
        "seq": None,
        "kv_seq": None,  # set to "data" for context-parallel long decode
        "embed": None,
        "act_heads": "model",
        "act_kv": "model",
        "act_mlp": "model",
        "act_inner": "model",
        # params
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "expert_mlp": None,
        "expert_embed": None,  # -> "data" enables expert-weight FSDP
        "inner": "model",
        "state": None,
        "conv": None,
        "periods": None,
    }
)


def _axes_in_mesh(mesh: Optional[Mesh], axes: MeshAxes) -> MeshAxes:
    """Drop mesh axes the current mesh doesn't have (e.g. 'pod' single-pod)."""
    if axes is None or mesh is None:
        return axes if mesh is not None else None
    names = set(mesh.axis_names)
    if isinstance(axes, str):
        return axes if axes in names else None
    kept = tuple(a for a in axes if a in names)
    return kept if kept else None


def pspec_for(
    logical_axes: Sequence[Optional[str]],
    rules: ShardingRules,
    mesh: Optional[Mesh],
) -> P:
    if mesh is None:
        return P()
    out = []
    used: set = set()
    for ax in logical_axes:
        m = _axes_in_mesh(mesh, rules.get(ax))
        # a mesh axis may appear at most once in a PartitionSpec
        if m is not None:
            flat = (m,) if isinstance(m, str) else m
            flat = tuple(a for a in flat if a not in used)
            used.update(flat)
            m = flat[0] if len(flat) == 1 else (flat if flat else None)
        out.append(m)
    return P(*out)


def sharding_for(
    logical_axes: Sequence[Optional[str]],
    rules: ShardingRules,
    mesh: Optional[Mesh],
) -> Optional[NamedSharding]:
    if mesh is None:
        return None
    return NamedSharding(mesh, pspec_for(logical_axes, rules, mesh))


def shard_constraint(x, logical_axes, rules: ShardingRules, mesh: Optional[Mesh]):
    """with_sharding_constraint if a mesh is active; identity otherwise."""
    if mesh is None:
        return x
    spec = pspec_for(logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# TP execution planning
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ExecConfig:
    """An architecture resolved against a tensor-parallel degree.

    heads_exec: query heads padded to a multiple of tp (pad heads get
      zeroed o_proj rows, so outputs are unchanged).
    kv_exec: KV heads block-replicated to max(kv, tp). Block replication
      (head j of kv_exec = original j // repeat) keeps GQA grouping local and
      consistent across *every* TP level — the invariant the paper's TP
      switching relies on (DESIGN.md §2).
    """

    cfg: ModelConfig
    tp: int
    heads_exec: int
    kv_exec: int

    @property
    def kv_repeat(self) -> int:
        return self.kv_exec // max(self.cfg.num_kv_heads, 1)

    @property
    def head_pad(self) -> int:
        return self.heads_exec - self.cfg.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.heads_exec // self.kv_exec


def make_exec_config(cfg: ModelConfig, tp: int) -> ExecConfig:
    if cfg.family == "ssm":
        return ExecConfig(cfg, tp, 0, 0)
    h = ceil_to(cfg.num_heads, tp)
    kv = cfg.num_kv_heads
    if tp > kv:
        if tp % kv != 0:
            raise ValueError(f"tp={tp} not a multiple of kv_heads={kv}")
        kv = tp
    # query-head grouping must stay uniform: heads_exec % kv_exec == 0
    if h % kv != 0:
        h = ceil_to(h, kv)
    return ExecConfig(cfg, tp, h, kv)
