"""Compile-only checks for a described TPU v5e (no chip attached): the
engine's own decode and prefill steps for h2o-danube-1.8b at its published
widths in bf16, the decode step at Yi-34B's widths, and the Pallas kernels at
danube's shapes. The TPU compiler refuses here what it would refuse on the
chip: illegal block tiling, more memory than a device has.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core.migration import cache_shardings
from repro.core.weight_store import WeightStore, make_exec_mesh
from repro.kernels.kv_gather.kernel import kv_gather_p, kv_scatter_p
from repro.kernels.paged_attention.kernel import paged_decode_attention_p
from repro.kernels.tp_shard_matmul.kernel import tp_shard_matmul_p
from repro.kernels.tp_shard_matmul.ops import pick_blocks
from repro.models import init_cache_defs, model_param_defs
from repro.models.params import is_def
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config
from repro.serving.engine import EngineConfig, make_decode_step, make_prefill_step

HBM_BYTES = 16e9  # one v5e chip
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def danube():
    return get_config("h2o-danube-1.8b")


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _structs(defs, shardings, dtype):
    return jax.tree_util.tree_map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, dtype, sharding=s),
        defs, shardings, is_leaf=is_def,
    )


def _engine_args(cfg, devices, tp, max_tp):
    """The engine's weight store, mesh and argument shapes for a step at `tp`
    with the cache laid out for `max_tp`, as ServingEngine builds them."""
    econf = EngineConfig()
    store = WeightStore(
        cfg, model_param_defs(cfg, make_exec_config(cfg, 1)), DEFAULT_RULES, devices
    )
    mesh = make_exec_mesh(devices, tp)
    cache_ec = make_exec_config(cfg, max_tp)
    cdefs = init_cache_defs(cfg, cache_ec, econf.n_slots, econf.max_len)
    storage = _structs(store.storage_defs(), store.storage_shardings(mesh), BF16)
    caches = _structs(cdefs, cache_shardings(cdefs, DEFAULT_RULES, mesh), BF16)
    rep = NamedSharding(mesh, P())
    return store, mesh, cache_ec, storage, caches, rep, econf


def _decode_step(cfg, devices, tp):
    store, mesh, cache_ec, storage, caches, rep, econf = _engine_args(cfg, devices, tp, tp)
    compiled = make_decode_step(cfg, store, tp, mesh, cache_ec).lower(
        storage, caches,
        jax.ShapeDtypeStruct((econf.n_slots, 1), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((econf.n_slots,), jnp.int32, sharding=rep),
    ).compile()
    return compiled, jax.tree_util.tree_leaves(caches)[0]


def test_engine_decode_step_one_chip_fits(topo, danube):
    compiled, _ = _decode_step(danube, topo.devices[:1], tp=1)
    assert _bytes(compiled) < HBM_BYTES


def test_engine_prefill_step_one_chip_fits(topo, danube):
    store, mesh, cache_ec, storage, _, rep, econf = _engine_args(
        danube, topo.devices[:1], tp=1, max_tp=1
    )
    L = max(econf.prefill_buckets)
    pre = make_prefill_step(danube, store, 1, mesh, cache_ec)
    compiled = pre.lower(
        storage,
        jax.ShapeDtypeStruct((1, L), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
    ).compile()
    assert _bytes(compiled) < HBM_BYTES


def test_engine_decode_step_tp4_on_2x2(topo, danube):
    compiled, _ = _decode_step(danube, topo.devices, tp=4)
    assert _bytes(compiled) < HBM_BYTES
    assert "all-reduce" in compiled.as_text()  # TP 4 reduces partial sums


# ceilings on decode temporaries per device: danube's head_dim of 80 takes
# the row-by-row write, which the head-major cache must not make any dearer
DECODE_TEMP_CEILING = {("danube", 1): 935_424, ("danube", 4): 851_597_824}


@pytest.mark.parametrize(
    "model,chips", [("danube", 1), ("danube", 4), ("yi_width", 1), ("yi_width", 4)])
def test_decode_step_updates_stacked_caches_in_place(topo, danube, model, chips):
    """The caches ride through the layer scan in the layout they are stored
    in: no copy of a whole stacked cache around the loop. Each layer's
    attention reads its K/V slice where it lies: no copy and no fusion
    outputs one layer's slice, and at TP 1 the temporaries are fewer bytes
    than one layer's K slice (at TP 4 the selected weight shards are
    temporaries too)."""
    cfg = danube if model == "danube" else dataclasses.replace(
        get_config("yi-34b"), num_layers=2)  # Yi-34B widths: heads of 128
    compiled, k = _decode_step(cfg, topo.devices[:chips], tp=chips)
    shard = k.sharding.shard_shape(k.shape)
    stacked = "bf16[" + ",".join(map(str, shard)) + "]"
    text = compiled.as_text()
    entry = re.search(r"\nENTRY .*?\n}\n", text, re.S).group(0)
    copies = [ln for ln in entry.splitlines()
              if f"= {stacked}" in ln and re.search(r" copy(-start)?\(", ln)]
    assert not copies, copies
    layer = "bf16[" + ",".join(map(str, (1,) + shard[1:])) + "]"
    slices = [ln for ln in text.splitlines()
              if f"= {layer}" in ln and re.search(r" (copy|copy-start|fusion)\(", ln)]
    assert not slices, slices
    temp = compiled.memory_analysis().temp_size_in_bytes
    if chips == 1:
        assert temp < k.size // k.shape[0] * k.dtype.itemsize
    if (model, chips) in DECODE_TEMP_CEILING:
        assert temp <= DECODE_TEMP_CEILING[model, chips]


def test_paged_attention_compiles_at_danube_shape(topo, danube):
    one = SingleDeviceSharding(topo.devices[0])
    B, page, ctx = 16, 16, 2048
    KV, hd = danube.num_kv_heads, danube.head_dim
    G = danube.num_heads // KV
    n_pages = ctx // page
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    fn = jax.jit(lambda q, k, v, t, n: paged_decode_attention_p(
        q, k, v, t, n, softcap=None, interpret=False))
    compiled = fn.lower(
        s((B, KV, G, hd), BF16), s((B * n_pages, page, KV, hd), BF16),
        s((B * n_pages, page, KV, hd), BF16), s((B, n_pages), jnp.int32),
        s((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kv_gather_scatter_compile_at_danube_page(topo, danube):
    one = SingleDeviceSharding(topo.devices[0])
    F = 16 * danube.num_kv_heads * danube.head_dim  # one 16-token page
    pool = jax.ShapeDtypeStruct((2048, F), BF16, sharding=one)
    ids = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one)
    staged = jax.ShapeDtypeStruct((512, F), BF16, sharding=one)
    g = jax.jit(lambda p, i: kv_gather_p(p, i, interpret=False)).lower(pool, ids).compile()
    s = jax.jit(lambda p, st, i: kv_scatter_p(p, st, i, interpret=False)).lower(
        pool, staged, ids).compile()
    assert "tpu_custom_call" in g.as_text() and "tpu_custom_call" in s.as_text()


@pytest.mark.parametrize("mode,tp", [("col", 2), ("row", 4)])
def test_tp_shard_matmul_compiles_at_danube_mlp(topo, danube, mode, tp):
    one = SingleDeviceSharding(topo.devices[0])
    d, f, M = danube.d_model, danube.d_ff, 16
    if mode == "col":  # w_gate / w_in: select d_ff / tp columns
        k, w_shape, n_out = d, (d, f), f // tp
    else:  # w_out: select d_ff / tp rows
        k, w_shape, n_out = f // tp, (f, d), d
    bm, bn, bk = pick_blocks(M, k, n_out, w_shape[1], mode)
    fn = jax.jit(lambda x, w, off: tp_shard_matmul_p(
        x, w, off, mode=mode, n_out=n_out, bm=bm, bn=bn, bk=bk, interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((M, k), BF16, sharding=one),
        jax.ShapeDtypeStruct(w_shape, BF16, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tp_shard_matmul_refuses_untileable_column_shard(danube):
    # d_ff / 4 = 1728 columns: no multiple of 128 divides it
    with pytest.raises(ValueError, match="cannot be tiled"):
        pick_blocks(16, danube.d_model, danube.d_ff // 4, danube.d_ff, "col")
