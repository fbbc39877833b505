"""TP switch controller: warm AOT executables + zero-copy weight rebinding.

The paper keeps one pre-profiled (CUDA-graph captured, torch.compiled)
process *per TP level* alive, and a switch just routes work to a different
warm process. The JAX analogue: one AOT-compiled executable per
(TP level, stage, batch bucket), compiled up front; a switch dispatches to a
different executable. Weights never move (WeightStore.rebind), caches are
migrated by core/migration.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core.weight_store import WeightStore, make_exec_mesh


class SwitchAborted(RuntimeError):
    """A TP switch failed mid-flight (e.g. a device died during cache
    migration). The controller guarantees it has rolled back to the
    pre-switch executable set and weight binding before raising, so the
    caller may keep serving at the old TP or retry on a reduced pool."""


@dataclass
class SwitchStats:
    n_switches: int = 0
    n_aborts: int = 0


class ExecutableCache:
    """AOT-compiled executables per (tp, key). Compilation happens once at
    startup ("offline", like the paper's CUDA-graph capture); switches only
    dispatch."""

    def __init__(self):
        self._exe: Dict[Tuple[int, Any], Any] = {}

    def put(self, tp: int, key: Any, lowered) -> None:
        self._exe[(tp, key)] = lowered.compile()

    def get(self, tp: int, key: Any):
        return self._exe[(tp, key)]

    def has(self, tp: int, key: Any) -> bool:
        return (tp, key) in self._exe

    def tps(self):
        return sorted({tp for tp, _ in self._exe})


class TPSwitchController:
    """Coordinates a TP switch: rebind weights (zero-copy), migrate caches,
    point dispatch at the new executable set."""

    def __init__(self, store: WeightStore, devices, candidate_tps):
        self.store = store
        self.devices = list(devices)
        self.meshes = {tp: make_exec_mesh(self.devices, tp) for tp in candidate_tps}
        self.cache = ExecutableCache()
        self.stats = SwitchStats()
        self.current_tp: Optional[int] = None
        self.storage = None

    def install(self, storage, tp: int) -> None:
        self.storage = self.store.build(storage, self.meshes[tp]) if is_canonical(
            storage
        ) else storage
        self.current_tp = tp

    def switch(self, to_tp: int, migrate_fn: Optional[Callable] = None):
        """migrate_fn: caches -> (migrated_caches, seconds).

        Transactional: if migrate_fn raises (device loss mid-migration),
        the pre-switch storage binding and current_tp are restored and
        ``SwitchAborted`` is raised — the controller is never left pointing
        at the new TP with un-migrated caches. Rollback is free because
        rebind is zero-copy: the old storage arrays still alias the same
        per-device buffers.
        """
        assert self.storage is not None
        prev_storage, prev_tp = self.storage, self.current_tp
        self.storage = self.store.rebind(self.storage, self.meshes[to_tp])
        migrated = None
        if migrate_fn is not None:
            try:
                migrated, _ = migrate_fn(self.meshes[to_tp])
            except Exception as e:
                self.storage, self.current_tp = prev_storage, prev_tp
                self.stats.n_aborts += 1
                raise SwitchAborted(
                    f"switch {prev_tp}->{to_tp} aborted during cache "
                    f"migration: {e}"
                ) from e
        self.current_tp = to_tp
        self.stats.n_switches += 1
        return migrated


def is_canonical(tree) -> bool:
    # heuristic: canonical params are plain (unsharded/single-device) arrays
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(
        getattr(x, "sharding", None) is None or len(x.sharding.device_set) == 1
        for x in leaves
    )
