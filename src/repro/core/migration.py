"""KV / SSM-state migration for TP switching (paper §3.2.2).

When the TP level changes, per-sequence state must be re-partitioned across
the new TP groups: attention KV by head, Mamba state by head/channel. The
paper's mechanism is stop-and-migrate with (a) aggregation of fragmented
pages into contiguous staging and (b) a pipelined copy/transmit double
buffer.

TPU realization:
  * aggregation: kernels/kv_gather (Pallas pipelined block DMA);
  * transfer: one resharding program over ICI (a jitted identity whose
    out_shardings are the new mesh's — lowered to collective-permute /
    all-to-all);
  * the analytic latency model below reproduces the paper's Fig. 7
    (naive per-page vs aggregated vs pipelined) for the simulator and
    benchmark; on-chip numbers come from the dry-run roofline constants.

Paper-inapplicability note (DESIGN.md §7): mamba2 has no KV cache; its
analogue is the O(1)-per-sequence SSD state, migrated the same way (and two
orders of magnitude smaller — migration is never the bottleneck for SSM).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ModelConfig
from repro.models.params import is_def
from repro.parallel.sharding import ShardingRules, pspec_for
from repro.profiles.perf_model import HardwareSpec, V5E


def cache_shardings(cache_defs, rules: ShardingRules, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda d: NamedSharding(mesh, pspec_for(d.axes, rules, mesh)),
        cache_defs,
        is_leaf=is_def,
    )


class MigrationAborted(RuntimeError):
    """The source cache is guaranteed untouched: migration is functional
    (device_put builds new arrays; nothing frees or mutates the source
    until the caller drops its reference), so after an abort the caller
    can retry on a reduced pool or restart the sequences from scratch."""


def reshard(tree, target_shardings):
    """Move `tree` (all leaves on one mesh) to `target_shardings` (all on
    one mesh over the same devices) so that every byte stays on the devices
    and crosses ICI.

    `jax.device_put` does so itself when the two meshes list their devices
    in different orders (TP 1 <-> TP 2 under `make_exec_mesh`): it reorders
    the shards and reshards with a compiled identity. For meshes in the same
    order (TP 1 <-> TP 4) it would copy every array through the host, so
    that case runs the compiled identity directly.
    """
    src = jax.tree_util.tree_leaves(tree)[0].sharding
    dst = jax.tree_util.tree_leaves(target_shardings)[0]
    if (isinstance(src, NamedSharding) and isinstance(dst, NamedSharding)
            and list(src.mesh.devices.flat) == list(dst.mesh.devices.flat)):
        # a module-level function, so that JAX's caches find the compiled
        # program again on the next switch between the same layouts
        return jax.jit(_identity, out_shardings=target_shardings)(tree)
    return jax.tree_util.tree_map(jax.device_put, tree, target_shardings)


def _identity(tree):
    return tree


def migrate_cache(cache, target_shardings):
    """Stop-and-migrate: reshard every cache leaf to the new TP layout.

    Lowers to ICI collectives on TPU. Returns the migrated cache and the
    host-measured wall time (meaningful on the real mini-cluster; the
    simulator uses `migration_time_model`).

    Abort-safe: a mid-flight failure (source or target device dying, OOM
    on the target layout) raises ``MigrationAborted`` with the original
    cache intact — partially-materialized target arrays are dropped.
    """
    t0 = time.perf_counter()
    try:
        out = reshard(cache, target_shardings)
        jax.block_until_ready(out)
    except MigrationAborted:
        raise
    except Exception as e:
        raise MigrationAborted(f"cache migration aborted: {e}") from e
    return out, (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Analytic migration-latency model (paper Fig. 7)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MigrationModel:
    hw: HardwareSpec = V5E
    page_bytes: int = 32 * 1024  # 16 tokens x 8 kv heads x 128 x 2B
    # per-op issue overhead: dominated by host-side descriptor setup for
    # small async copies; 50us/page reproduces the paper's measured Fig. 7
    # endpoints (0.88s naive @ 0.5GB, 24.8ms pipelined @ 5GB) on our link
    # constants — see EXPERIMENTS.md §Fig7.
    per_transfer_overhead_s: float = 50e-6
    staging_bytes: int = 16 * 1024 * 1024  # double-buffer stage size

    def ici_bw(self) -> float:
        return self.hw.ici_bw * self.hw.ici_links

    def naive_per_page_s(self, total_bytes: float) -> float:
        """cudaMemcpyAsync-per-page analogue: one transfer per page."""
        n_pages = max(int(np.ceil(total_bytes / self.page_bytes)), 1)
        # small transfers do not reach link bandwidth; model an effective
        # bandwidth that saturates with transfer size
        eff_bw = self.ici_bw() * self.page_bytes / (self.page_bytes + 256 * 1024)
        return n_pages * (self.per_transfer_overhead_s + self.page_bytes / eff_bw)

    def aggregated_s(self, total_bytes: float) -> float:
        """Gather all pages into one buffer, then one big transfer."""
        gather = total_bytes * 2 / (self.hw.hbm_bw * self.hw.bw_eff)  # r+w
        send = total_bytes / self.ici_bw() + self.per_transfer_overhead_s
        return gather + send

    def pipelined_s(self, total_bytes: float) -> float:
        """Nitsum: double-buffered overlap of gather and transmit."""
        gather = total_bytes * 2 / (self.hw.hbm_bw * self.hw.bw_eff)
        send = total_bytes / self.ici_bw()
        stage = self.staging_bytes
        fill = stage * 2 / (self.hw.hbm_bw * self.hw.bw_eff)
        return max(gather, send) + fill + self.per_transfer_overhead_s

    def migration_s(self, total_bytes: float, strategy: str = "pipelined") -> float:
        return {
            "naive": self.naive_per_page_s,
            "aggregated": self.aggregated_s,
            "pipelined": self.pipelined_s,
        }[strategy](total_bytes)


def kv_migration_bytes(
    cfg: ModelConfig, n_seqs: int, ctx_len: int, from_tp: int, to_tp: int,
    dtype_bytes: int = 2,
) -> float:
    """Bytes that must cross chips when re-partitioning KV heads.

    Head-repartitioning moves the fraction of heads whose owner changes;
    upper bound (paper's Fig. 6 worst case) is the full per-group cache.
    """
    if cfg.n_attn_layers == 0:
        # SSM: migrate recurrent state instead
        from repro.profiles.perf_model import PerfModel

        return n_seqs * PerfModel(cfg).state_bytes()
    win = cfg.attn.window or ctx_len
    eff = min(ctx_len, win)
    per_seq = 2 * cfg.num_kv_heads * cfg.head_dim * dtype_bytes * eff * cfg.n_attn_layers
    lo, hi = min(from_tp, to_tp), max(from_tp, to_tp)
    moved_frac = 1.0 - lo / hi  # heads staying on the same chip
    if cfg.mamba is not None:  # hybrid: add state bytes
        from repro.profiles.perf_model import PerfModel

        per_seq += PerfModel(cfg).state_bytes()
    return n_seqs * per_seq * moved_frac
