"""Storage-TP weight store: zero-copy TP switching (paper §3.2.1, TPU form).

The paper keeps one full weight copy per GPU and lets TP-specialized kernels
select their shard at execution time. On 16 GB/chip TPUs a full copy rarely
fits, so we generalize: weights are stored sharded at the *minimum candidate
TP* (``storage_tp``; 1 reproduces the paper exactly). The key invariant:

    The per-device bytes of the storage layout are IDENTICAL at every
    execution TP level.

Construction: for a pool of N chips, the model-sharded dimension of each
weight is laid out so that pool position d holds canonical shard
``floor(d·s/N)`` (block replication, s = storage_tp). Execution meshes are
built *model-major* — device d's model coordinate is ``floor(d·tp/N)`` — so
every execution shard is a contiguous sub-slice of the local storage shard,
selected inside the compiled program by a device-index-dependent
``dynamic_slice`` (or fused into the matmul by kernels/tp_shard_matmul).
Switching TP therefore moves **zero** weight bytes: arrays are re-bound to
the new mesh via ``make_array_from_single_device_arrays`` over the existing
per-device buffers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef, is_def, tree_map_defs
from repro.parallel.sharding import (
    ShardingRules, make_exec_config, pspec_for,
)


def model_dim_of(d: ParamDef, rules: ShardingRules) -> Optional[int]:
    """Index of the (single) model-sharded dim of a canonical param."""
    dims = []
    for i, ax in enumerate(d.axes):
        m = rules.get(ax) if ax is not None else None
        flat = (m,) if isinstance(m, str) else (m or ())
        if "model" in flat:
            dims.append(i)
    assert len(dims) <= 1, (d, dims)
    return dims[0] if dims else None


def make_exec_mesh(devices: Sequence, tp: int, with_pod: bool = False) -> Mesh:
    """Model-major mesh: device d gets model coordinate floor(d*tp/N)."""
    n = len(devices)
    assert n % tp == 0, (n, tp)
    arr = np.array(devices).reshape(tp, n // tp).T  # [i, t] = devs[t*(n//tp)+i]
    return Mesh(arr, ("data", "model"))


@dataclass
class _LeafPlan:
    dim: Optional[int]
    n_units: int  # canonical length of the sharded dim


class WeightStore:
    def __init__(
        self,
        cfg: ModelConfig,
        canonical_defs,
        rules: ShardingRules,
        devices: Sequence,
        storage_tp: int = 1,
    ):
        self.cfg = cfg
        self.rules = rules
        self.devices = list(devices)
        self.N = len(self.devices)
        self.s = storage_tp
        assert self.N % storage_tp == 0
        self.canonical_defs = canonical_defs
        self.leaves, self.treedef = jax.tree_util.tree_flatten(
            canonical_defs, is_leaf=is_def
        )
        self.plans: List[_LeafPlan] = []
        for d in self.leaves:
            k = model_dim_of(d, rules)
            self.plans.append(_LeafPlan(k, d.shape[k] if k is not None else 0))

    # ---- storage layout -------------------------------------------------
    def storage_defs(self):
        out = []
        for d, plan in zip(self.leaves, self.plans):
            if plan.dim is None:
                out.append(d)
            else:
                shape = list(d.shape)
                shape[plan.dim] = plan.n_units * (self.N // self.s)
                out.append(ParamDef(tuple(shape), d.axes, d.init, d.scale))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def storage_pspec(self, leaf_idx: int) -> P:
        plan = self.plans[leaf_idx]
        if plan.dim is None:
            return P()
        spec = [None] * len(self.leaves[leaf_idx].shape)
        spec[plan.dim] = ("model", "data")
        return P(*spec)

    def storage_pspecs(self):
        specs = [self.storage_pspec(i) for i in range(len(self.leaves))]
        return jax.tree_util.tree_unflatten(self.treedef, specs)

    def storage_shardings(self, mesh: Mesh):
        specs = [
            NamedSharding(mesh, self.storage_pspec(i)) for i in range(len(self.leaves))
        ]
        return jax.tree_util.tree_unflatten(self.treedef, specs)

    def build(self, canonical_params, mesh: Mesh):
        """Place canonical params on `mesh` in the storage layout (done once
        at load).

        Each device receives only its own storage shard, so no device ever
        holds the tiled global array. A shard that is a whole canonical leaf
        already on its device (storage_tp=1) reuses that buffer: the caller
        may drop its canonical params afterwards without freeing anything.
        """
        flat = jax.tree_util.tree_leaves(canonical_params)
        out = []
        for idx, (x, plan) in enumerate(zip(flat, self.plans)):
            sh = NamedSharding(mesh, self.storage_pspec(idx))
            if plan.dim is None:
                out.append(jax.device_put(x, sh))
                continue
            w = plan.n_units // self.s  # units per storage shard
            shape = list(x.shape)
            shape[plan.dim] = w * self.N
            bufs = []
            for d, index in sh.devices_indices_map(tuple(shape)).items():
                # pool position j holds canonical shard floor(j*s/N)
                j = (index[plan.dim].start or 0) // w
                c = j * self.s // self.N
                src = x if w == plan.n_units else jax.lax.slice_in_dim(
                    x, c * w, (c + 1) * w, axis=plan.dim
                )
                bufs.append(jax.device_put(src, d))
            out.append(jax.make_array_from_single_device_arrays(tuple(shape), sh, bufs))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # ---- pool shrink after device / host loss ---------------------------
    def shrink(self, surviving_devices: Sequence) -> "WeightStore":
        """New store over the surviving pool after a device or host loss.

        Weight shards on the dead devices are gone, so this does NOT try to
        salvage storage arrays — the caller reloads canonical params into
        the new layout via ``build`` (the weight-reload storm the simulator
        prices on recovery, docs/faults.md). ``storage_tp`` is clamped to
        the largest value that still divides the surviving pool size, so
        the per-device-bytes invariant keeps holding on the smaller pool.
        """
        alive = set(surviving_devices)
        devs = [d for d in self.devices if d in alive]  # keep pool order
        assert devs, "shrink: no surviving devices"
        s = min(self.s, len(devs))
        while len(devs) % s:
            s -= 1
        return WeightStore(
            self.cfg, self.canonical_defs, self.rules, devs, storage_tp=s
        )

    # ---- zero-copy rebinding across TP meshes ---------------------------
    def rebind(self, storage, new_mesh: Mesh):
        """Re-associate storage arrays with a new TP mesh WITHOUT moving data.

        The per-device buffers are reused verbatim; only the sharding
        metadata changes. This is the TP switch: O(µs), no HBM traffic.
        """
        flat = jax.tree_util.tree_leaves(storage)
        out = []
        for i, x in enumerate(flat):
            sh = NamedSharding(new_mesh, self.storage_pspec(i))
            # relabel even an equivalent sharding: each TP's executables were
            # compiled against arrays bound to that TP's mesh, and a stale
            # mesh label would make them compile again.
            # device order is identical by construction; reuse buffers
            dev_to_buf = {s.device: s.data for s in x.addressable_shards}
            bufs = []
            for d, idx in sh.devices_indices_map(x.shape).items():
                bufs.append(dev_to_buf[d])
            out.append(
                jax.make_array_from_single_device_arrays(x.shape, sh, bufs)
            )
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # ---- execution-time shard selection ---------------------------------
    def select_fn(self, tp: int, mesh: Mesh):
        """Returns f(storage) -> exec params; embed in the serving step jit.

        Selection is a per-device local dynamic_slice (pure addressing; XLA
        fuses it with the consumer matmul — see kernels/tp_shard_matmul for
        the explicitly fused form).
        """
        assert tp >= self.s and tp % self.s == 0, (tp, self.s)
        ec = make_exec_config(self.cfg, tp)
        from repro.models.model import model_param_defs

        exec_defs = model_param_defs(self.cfg, ec)
        exec_leaves = jax.tree_util.tree_leaves(exec_defs, is_leaf=is_def)
        in_specs = tuple(self.storage_pspec(i) for i in range(len(self.leaves)))
        out_specs = tuple(
            pspec_for(d.axes, self.rules, mesh) for d in exec_leaves
        )
        plans = self.plans
        s = self.s

        def inner(*flat_storage):
            t = jax.lax.axis_index("model")
            outs = []
            for x, plan in zip(flat_storage, plans):
                if plan.dim is None:
                    outs.append(x)
                    continue
                n = plan.n_units
                width = max(n // tp, 1)
                off = (t * n) // tp - (t * s // tp) * (n // s)
                outs.append(jax.lax.dynamic_slice_in_dim(x, off, width, plan.dim))
            return tuple(outs)

        smapped = jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

        def select(storage):
            flat = jax.tree_util.tree_leaves(storage)
            outs = smapped(*flat)
            return jax.tree_util.tree_unflatten(self.treedef, list(outs))

        return select

    # ---- memory accounting ----------------------------------------------
    def bytes_per_device(self, dtype_bytes: int = 2) -> int:
        total = 0
        for d, plan in zip(self.leaves, self.plans):
            n = int(np.prod(d.shape)) * dtype_bytes
            if plan.dim is None:
                total += n  # replicated
            else:
                total += n // self.s
        return total
