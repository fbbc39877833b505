"""Work the algorithm needs, computed from shapes: floating-point operations
and HBM bytes of a dense GQA decoder's prefill and decode.

Prefill counts the true prompt tokens (not the bucket padding), causal
attention over them, and the output head for the last position only.
Decode counts, per step, the weights once per data-parallel group plus the
live key/value context of the occupied slots (not the whole cache), and the
embedding rows of the tokens fed. An implementation that reads or computes
less than this cannot exist, so a share built on these counts stays <= 100%.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

BF16 = 2


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    window: int | None = None  # sliding window; None = full attention

    @property
    def layer_matmul_params(self) -> int:
        d, h, kv, hd = self.d_model, self.heads, self.kv_heads, self.head_dim
        return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * self.d_ff

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * BF16

    def attended(self, ctx: int) -> int:
        return ctx if self.window is None else min(ctx, self.window)


def prefill_flops(m: Dims, prompt_len: int) -> float:
    L = prompt_len
    mm = 2 * L * m.layers * m.layer_matmul_params
    # causal scores and weighted values: query i attends to i+1 keys
    pairs = sum(m.attended(i + 1) for i in range(L)) if m.window else L * (L + 1) // 2
    attn = 2 * 2 * m.heads * m.head_dim * m.layers * pairs
    head = 2 * m.d_model * m.vocab
    return float(mm + attn + head)


def decode_flops(m: Dims, contexts: Iterable[int]) -> float:
    """One decode step; `contexts` holds, per occupied slot, the number of
    positions its new token attends to (its own included)."""
    per_tok = 2 * (m.layers * m.layer_matmul_params + m.d_model * m.vocab)
    tot = 0
    for c in contexts:
        tot += per_tok + 2 * 2 * m.heads * m.head_dim * m.layers * m.attended(c)
    return float(tot)


def weight_bytes(m: Dims) -> int:
    """Every matrix a decode step multiplies by, the norms and the head; the
    embedding table is read a row per token, counted in decode_bytes."""
    norms = (2 * m.layers + 1) * m.d_model
    return BF16 * (m.layers * m.layer_matmul_params + m.d_model * m.vocab + norms)


def decode_bytes(m: Dims, contexts: Iterable[int], groups: int = 1) -> float:
    """One decode step over `groups` data-parallel groups, each of which has
    to read its weights once."""
    ctx = list(contexts)
    kv = sum(m.attended(c) for c in ctx) * m.kv_bytes_per_token
    return float(groups * weight_bytes(m) + kv + len(ctx) * m.d_model * BF16)
