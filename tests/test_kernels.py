"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)
plus hypothesis property tests on the kernels' invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.tp_shard_matmul.ops import tp_shard_matmul
from repro.kernels.tp_shard_matmul.ref import tp_shard_matmul_ref
from repro.kernels.kv_gather.ops import kv_gather, kv_scatter
from repro.kernels.kv_gather.ref import kv_gather_ref, kv_scatter_ref
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# tp_shard_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n_store,n_out,shard",
    [
        (64, 128, 512, 128, 0),
        (64, 128, 512, 128, 3),
        (128, 256, 1024, 256, 2),
        (32, 64, 144, 144, 0),  # non-128-aligned: the whole width as one lane block
        (256, 512, 1024, 512, 1),
    ],
)
def test_tp_shard_matmul_col_sweep(dtype, m, k, n_store, n_out, shard):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n_out + shard))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (k, n_store), jnp.float32).astype(dtype)
    off = shard * n_out
    got = tp_shard_matmul(x, w, off, n_out=n_out, mode="col")
    want = tp_shard_matmul_ref(x, w, off, mode="col", n_out=n_out)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k_store,k,n,shard",
    [(64, 512, 128, 128, 0), (64, 512, 128, 128, 2), (32, 256, 64, 96, 1)],
)
def test_tp_shard_matmul_row_sweep(dtype, m, k_store, k, n, shard):
    kx, kw = jax.random.split(jax.random.PRNGKey(7 * m + k + n + shard))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (k_store, n), jnp.float32).astype(dtype)
    off = shard * k
    got = tp_shard_matmul(x, w, off, n_out=n, mode="row")
    want = tp_shard_matmul_ref(x, w, off, mode="row", n_out=n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_tp_shard_matmul_equals_presliced_weights():
    """The paper's invariant: executing from the unified store at any shard
    offset must be bit-identical to a matmul against pre-sliced weights."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 512), jnp.float32)
    for tp in (1, 2, 4):
        n_out = 512 // tp
        for s in range(tp):
            got = tp_shard_matmul(x, w, s * n_out, n_out=n_out, mode="col")
            direct = tp_shard_matmul(x, w[:, s * n_out:(s + 1) * n_out], 0,
                                     n_out=n_out, mode="col")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))


@settings(max_examples=20, deadline=None)
@given(
    mb=st.integers(1, 4), kb=st.integers(1, 4), nb=st.integers(1, 4),
    tp=st.sampled_from([1, 2, 4]), shard=st.integers(0, 3), seed=st.integers(0, 99),
)
def test_tp_shard_matmul_property(mb, kb, nb, tp, shard, seed):
    # tile-legal widths: a shard of 128 * nb columns, or at TP 1 the whole
    # weight, which may be any width
    m, k = 8 * mb, 8 * kb
    n_out = 128 * nb if tp > 1 else 32 * nb
    n_full = n_out * tp
    shard = shard % tp
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n_full), jnp.float32)
    got = tp_shard_matmul(x, w, shard * n_out, n_out=n_out, mode="col")
    want = x @ w[:, shard * n_out:(shard + 1) * n_out]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# kv_gather / kv_scatter
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,F,n", [(16, 128, 4), (64, 256, 64), (8, 512, 1)])
def test_kv_gather_sweep(dtype, P, F, n):
    pool = jax.random.normal(jax.random.PRNGKey(P + F), (P, F), jnp.float32).astype(dtype)
    ids = np.random.RandomState(n).permutation(P)[:n]
    got = kv_gather(pool, ids)
    want = kv_gather_ref(pool, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_scatter_roundtrip(dtype):
    P, F, n = 32, 128, 8
    pool = jax.random.normal(jax.random.PRNGKey(0), (P, F), jnp.float32).astype(dtype)
    staged = jax.random.normal(jax.random.PRNGKey(1), (n, F), jnp.float32).astype(dtype)
    ids = np.random.RandomState(2).permutation(P)[:n]
    want = kv_scatter_ref(pool, staged, ids)
    got = kv_scatter(pool + 0, staged, ids)  # +0: keep original for the oracle
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(P=st.integers(2, 32), n_frac=st.floats(0.1, 1.0), seed=st.integers(0, 99))
def test_kv_gather_scatter_inverse_property(P, n_frac, seed):
    """scatter(gather(pool, ids), ids) must reproduce pool exactly."""
    F = 64
    n = max(1, int(P * n_frac))
    pool = jax.random.normal(jax.random.PRNGKey(seed), (P, F), jnp.float32)
    ids = np.random.RandomState(seed).permutation(P)[:n]
    staged = kv_gather(pool, ids)
    back = kv_scatter(pool + 0, staged, ids)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(pool))


# ---------------------------------------------------------------------------
# paged_decode_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,KV,G,hd,page,n_pages",
    [
        (2, 2, 4, 32, 8, 4),
        (1, 1, 8, 64, 16, 2),
        (4, 4, 1, 16, 4, 8),  # MHA-style
    ],
)
def test_paged_decode_attention_sweep(dtype, B, KV, G, hd, page, n_pages):
    rng = np.random.RandomState(B * 31 + n_pages)
    P = B * n_pages + 2
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(keys[1], (P, page, KV, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(keys[2], (P, page, KV, hd), jnp.float32).astype(dtype)
    tables = rng.permutation(P)[: B * n_pages].reshape(B, n_pages)
    lens = rng.randint(1, page * n_pages + 1, size=(B,))
    got = paged_decode_attention(q, kp, vp, tables, lens)
    want = paged_decode_attention_ref(q, kp, vp, tables, lens)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


def test_paged_decode_attention_softcap():
    B, KV, G, hd, page, n_pages = 2, 2, 2, 16, 8, 2
    P = B * n_pages
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, page, KV, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, page, KV, hd), jnp.float32)
    tables = np.arange(P).reshape(B, n_pages)
    lens = np.array([13, 16])
    got = paged_decode_attention(q, kp, vp, tables, lens, softcap=20.0)
    want = paged_decode_attention_ref(q, kp, vp, tables, lens, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(
    B=st.integers(1, 3), G=st.integers(1, 4), page=st.sampled_from([4, 8]),
    n_pages=st.integers(1, 4), seed=st.integers(0, 99),
)
def test_paged_attention_matches_dense_property(B, G, page, n_pages, seed):
    """Paged attention over a shuffled page table == dense attention over the
    same logical sequence (permutation invariance of the block table)."""
    KV, hd = 2, 16
    P = B * n_pages
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, KV, G, hd), jnp.float32)
    kp = jax.random.normal(keys[1], (P, page, KV, hd), jnp.float32)
    vp = jax.random.normal(keys[2], (P, page, KV, hd), jnp.float32)
    rng = np.random.RandomState(seed)
    tables = rng.permutation(P).reshape(B, n_pages)
    lens = rng.randint(1, page * n_pages + 1, size=(B,))
    got = paged_decode_attention(q, kp, vp, tables, lens)
    want = paged_decode_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
