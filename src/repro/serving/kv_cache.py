"""KV cache management.

Two layouts:
  * SlotCache — dense per-slot caches used by the SPMD mini-cluster engine
    (global slot dim sharded over the data axis; KV heads over model). TP
    switching migrates it with one resharding program (core/migration).
  * PagedPool — PagedAttention-style paged pool + block tables; the layout
    the migration kernels (kv_gather/kv_scatter) aggregate from, and what a
    full-scale deployment uses. Exercised by the paged_attention kernel path
    and the Fig. 7 benchmark.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_cache_defs
from repro.models.params import is_def
from repro.parallel.sharding import ExecConfig


# ---------------------------------------------------------------------------
# Dense slot cache (engine runtime)
# ---------------------------------------------------------------------------
@dataclass
class SlotCache:
    cfg: ModelConfig
    ec: ExecConfig
    n_slots: int
    max_len: int
    arrays: dict = None  # pytree: {"pos{i}": {...: (P, B, S, KV, hd)}}
    lengths: np.ndarray = None  # host-side per-slot lengths
    free: Deque[int] = None

    @classmethod
    def create(cls, cfg, ec, n_slots, max_len, dtype, shardings):
        """Zero caches created in place, one leaf per entry of `shardings`."""
        arrays = jax.tree_util.tree_map(
            lambda d, s: jnp.zeros(d.shape, dtype, device=s),
            init_cache_defs(cfg, ec, n_slots, max_len), shardings, is_leaf=is_def,
        )
        return cls(
            cfg, ec, n_slots, max_len, arrays,
            np.zeros(n_slots, np.int64), deque(range(n_slots)),
        )

    def cache_defs(self):
        return init_cache_defs(self.cfg, self.ec, self.n_slots, self.max_len)

    def alloc(self) -> Optional[int]:
        return self.free.popleft() if self.free else None

    def release(self, slot: int) -> None:
        self.lengths[slot] = 0
        self.free.append(slot)


# ---------------------------------------------------------------------------
# Paged pool + block tables
# ---------------------------------------------------------------------------
@dataclass
class PagedPool:
    """Per-layer paged KV pool with free-list allocation."""

    num_pages: int
    page_size: int
    kv_heads: int
    head_dim: int
    n_layers: int
    dtype: object = jnp.float32

    k_pages: jnp.ndarray = None  # (L, P, page, KV, hd)
    v_pages: jnp.ndarray = None
    free_pages: Deque[int] = field(default_factory=deque)
    tables: Dict[int, List[int]] = field(default_factory=dict)  # seq -> pages
    seq_lens: Dict[int, int] = field(default_factory=dict)

    def __post_init__(self):
        shape = (self.n_layers, self.num_pages, self.page_size, self.kv_heads, self.head_dim)
        if self.k_pages is None:
            self.k_pages = jnp.zeros(shape, self.dtype)
            self.v_pages = jnp.zeros(shape, self.dtype)
        if not self.free_pages:
            self.free_pages = deque(range(self.num_pages))
        elif not isinstance(self.free_pages, deque):
            self.free_pages = deque(self.free_pages)

    @property
    def pages_per_seq_max(self) -> int:
        return self.num_pages

    def alloc_seq(self, seq_id: int, n_tokens: int) -> bool:
        need = -(-n_tokens // self.page_size)
        if len(self.free_pages) < need:
            return False
        self.tables[seq_id] = [self.free_pages.popleft() for _ in range(need)]
        self.seq_lens[seq_id] = n_tokens
        return True

    def extend_seq(self, seq_id: int, n_new: int = 1) -> bool:
        cur = self.seq_lens[seq_id]
        new = cur + n_new
        need = -(-new // self.page_size) - len(self.tables[seq_id])
        if need > len(self.free_pages):
            return False
        for _ in range(need):
            self.tables[seq_id].append(self.free_pages.popleft())
        self.seq_lens[seq_id] = new
        return True

    def release_seq(self, seq_id: int) -> None:
        self.free_pages.extend(self.tables.pop(seq_id))
        self.seq_lens.pop(seq_id)

    def fragmentation(self) -> float:
        """Fraction of live pages that are non-contiguous with their
        predecessor — the quantity the paper's aggregation attacks."""
        frag = tot = 0
        for pages in self.tables.values():
            for a, b in zip(pages, pages[1:]):
                tot += 1
                frag += b != a + 1
        return frag / tot if tot else 0.0

    def block_table_array(self, seq_ids: List[int]) -> np.ndarray:
        width = max((len(self.tables[s]) for s in seq_ids), default=0)
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, s in enumerate(seq_ids):
            pg = self.tables[s]
            out[i, : len(pg)] = pg
        return out

    def migration_page_ids(self, seq_ids: List[int]) -> np.ndarray:
        """All pages that must be aggregated to migrate these sequences."""
        out: List[int] = []
        for s in seq_ids:
            out.extend(self.tables[s])
        return np.asarray(out, np.int32)
