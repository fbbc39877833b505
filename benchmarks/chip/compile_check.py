"""Compile the cells' programs for a described TPU v5e 2x2, with no chip
attached, and print each program's bytes per device: the seeded weight
maker, the engine's decode and prefill steps at the cells' shapes and TPs,
the cache migration between TPs, and the reference's layer at the check's
batch. The TPU compiler refuses here what it would refuse on the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/compile_check.py
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402
from reference import dense_gqa  # noqa: E402


def report(what, compiled):
    m = compiled.memory_analysis()
    print(f"{what}: arguments {m.argument_size_in_bytes} output {m.output_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"total {m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes}",
          flush=True)


def structs(defs, shardings, dtype):
    from repro.models.params import is_def
    return jax.tree_util.tree_map(lambda d, s: jax.ShapeDtypeStruct(d.shape, dtype, sharding=s),
                                  defs, shardings, is_leaf=is_def)


def engine_programs(name, cfg, mix, devices):
    from repro.core.migration import cache_shardings
    from repro.core.weight_store import WeightStore, make_exec_mesh
    from repro.models import init_cache_defs, model_param_defs
    from repro.parallel.sharding import DEFAULT_RULES, make_exec_config
    from repro.serving.engine import make_decode_step, make_prefill_step

    mcfg = harness.program_config(name, cfg)
    sv = cfg["serving"]
    tps = traffic.tps(mix)
    n_slots = sv["slots_per_chip"] * len(devices)
    store = WeightStore(mcfg, model_param_defs(mcfg, make_exec_config(mcfg, 1)), DEFAULT_RULES,
                        devices)
    cache_ec = make_exec_config(mcfg, max(tps))
    cdefs = init_cache_defs(mcfg, cache_ec, n_slots, sv["max_len"])
    caches = {}
    for tp in tps:
        mesh = make_exec_mesh(devices, tp)
        storage = structs(store.storage_defs(), store.storage_shardings(mesh), jnp.bfloat16)
        caches[tp] = structs(cdefs, cache_shardings(cdefs, DEFAULT_RULES, mesh), jnp.bfloat16)
        rep = NamedSharding(mesh, P())
        dec = make_decode_step(mcfg, store, tp, mesh, cache_ec).lower(
            storage, caches[tp], jax.ShapeDtypeStruct((n_slots, 1), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=rep)).compile()
        report(f"{name} decode TP {tp}, {n_slots} slots x {sv['max_len']}, per device", dec)
        L = max(sv["prefill_buckets"])
        pre = make_prefill_step(mcfg, store, tp, mesh, cache_ec).lower(
            storage, jax.ShapeDtypeStruct((1, L), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
        report(f"{name} prefill TP {tp}, bucket {L}, per device", pre)
    if len(tps) > 1:
        from repro.core.migration import _identity
        a, b = tps[0], tps[-1]
        out = jax.tree_util.tree_map(lambda s: s.sharding, caches[b])
        mig = jax.jit(_identity, out_shardings=out).lower(caches[a]).compile()
        report(f"{name} cache migration TP {a} -> {b}, per device", mig)


def benchmark_programs(name, cfg, device):
    one = SingleDeviceSharding(device)
    m = harness.dims_of(cfg)
    mcfg = harness.program_config(name, cfg)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    gen = weights.engine_params.lower(key, m, mcfg.vocab_padded).compile()
    report(f"{name} seeded weights, one jitted call", gen)
    B, S = cfg["check"]["min_seqs"], cfg["serving"]["max_len"]
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)  # noqa: E731
    w = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
         for k, (s, _) in weights.layer_shapes(m).items()}
    with jax.default_matmul_precision("highest"):
        lay = dense_gqa._layer.lower(
            f32((B, S, m.d_model)), w, jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one),
            m=m, eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]), precision="f32",
        ).compile()
    report(f"{name} reference layer, {B} sequences x {S}", lay)


def main():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = harness.load_spec(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for cell in spec["workloads"]:
        cfg = harness.config_of(spec, cell["config"])
        mix = harness.mix_of(cell["traffic"])
        devices = list(topo.devices[: cell["chips"]])
        engine_programs(cell["config"], cfg, mix, devices)
        benchmark_programs(cell["config"], cfg, devices[0])


if __name__ == "__main__":
    main()
