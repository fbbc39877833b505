"""Multi-device checks, run in a subprocess with host-platform devices.

Usage: XLA device count is set INSIDE this module (it must be the very first
thing before jax initializes), so invoke as a fresh subprocess:

    python -m repro.testing.multidev_checks <check> [ndev]

Checks:
  weight_store — the paper's §3.2.1 invariant: serving from the SAME storage
      arrays at TP ∈ {1,2,4,8} yields identical logits, and a TP switch
      rebinds buffers zero-copy (pointer-identical shards).
  moe_sharded  — shard_map EP MoE == local oracle.
  migration    — KV cache resharding across TP meshes preserves contents.
  fault_abort  — mid-flight aborts (docs/faults.md): a migration whose
      target is invalid leaves the original cache intact, and a weight
      reload on a shrunken pool (WeightStore.shrink) still serves correct
      logits.
  engine       — greedy trajectories are unchanged by mid-stream TP switches.
  engine_rollback — a TP switch whose migration fails leaves the engine at
      its old TP, serving the same tokens, and a retry succeeds.
"""
import os
import sys

NDEV = int(sys.argv[2]) if len(sys.argv) > 2 else 8
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={NDEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.configs.base import AttnSpec, ModelConfig  # noqa: E402
from repro.core.weight_store import WeightStore, make_exec_mesh  # noqa: E402
from repro.core.migration import cache_shardings, migrate_cache  # noqa: E402
from repro.models import forward, init_cache_defs, model_param_defs  # noqa: E402
from repro.models.model import logits_for  # noqa: E402
from repro.models.params import init_params, is_def  # noqa: E402
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config  # noqa: E402

RULES = DEFAULT_RULES


def _tiny_cfg() -> ModelConfig:
    return ModelConfig(
        name="tiny-dense",
        family="dense",
        num_layers=2,
        d_model=64,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        attn=AttnSpec(kind="full"),
    )


def check_weight_store() -> None:
    cfg = _tiny_cfg()
    devices = jax.devices()
    canon_defs = model_param_defs(cfg, make_exec_config(cfg, 1))
    canonical = init_params(canon_defs, jax.random.PRNGKey(0), jnp.float32)
    store = WeightStore(cfg, canon_defs, RULES, devices, storage_tp=1)

    B, S = 8, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    outs = {}
    storages = {}
    tps = [t for t in (1, 2, 4, 8) if t <= len(devices)]
    for tp in tps:
        mesh = make_exec_mesh(devices, tp)
        storage = store.build(canonical, mesh)
        storages[tp] = storage
        sel = store.select_fn(tp, mesh)
        ec = make_exec_config(cfg, tp)

        def step(storage, tokens):
            params = sel(storage)
            h, _ = forward(
                params, cfg, ec, rules=RULES, mesh=mesh, tokens=tokens,
                mode="prefill", block_q=16, block_k=16,
            )
            return logits_for(params, cfg, h, RULES, mesh)

        tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
        logits = jax.jit(step)(storage, tok_sh)
        outs[tp] = np.asarray(logits)[..., : cfg.vocab_size]

    for tp in tps[1:]:
        np.testing.assert_allclose(
            outs[tp], outs[tps[0]], rtol=2e-4, atol=2e-4,
            err_msg=f"TP={tp} logits diverge from TP=1",
        )
    print(f"weight_store: logits identical across TP {tps}")

    # zero-copy rebind: per-device buffers must be pointer-identical
    import time

    src = storages[tps[0]]
    mesh_to = make_exec_mesh(devices, tps[-1])
    before = {
        id(shard.data): shard.data.unsafe_buffer_pointer()
        for x in jax.tree_util.tree_leaves(src)
        for shard in x.addressable_shards
    }
    t0 = time.perf_counter()
    rebound = store.rebind(src, mesh_to)
    dt = time.perf_counter() - t0
    ptrs_before = sorted(
        s.data.unsafe_buffer_pointer()
        for x in jax.tree_util.tree_leaves(src)
        for s in x.addressable_shards
    )
    ptrs_after = sorted(
        s.data.unsafe_buffer_pointer()
        for x in jax.tree_util.tree_leaves(rebound)
        for s in x.addressable_shards
    )
    assert ptrs_before == ptrs_after, "rebind copied device buffers!"
    n_leaves = len(jax.tree_util.tree_leaves(src))
    print(f"weight_store: zero-copy rebind of {n_leaves} arrays in {dt*1e3:.3f} ms")

    # serving from the rebound storage still works and matches
    tp = tps[-1]
    sel = store.select_fn(tp, mesh_to)
    ec = make_exec_config(cfg, tp)

    def step2(storage, tokens):
        params = sel(storage)
        h, _ = forward(params, cfg, ec, rules=RULES, mesh=mesh_to,
                       tokens=tokens, mode="prefill", block_q=16, block_k=16)
        return logits_for(params, cfg, h, RULES, mesh_to)

    tok_sh = jax.device_put(tokens, NamedSharding(mesh_to, P("data", None)))
    logits = np.asarray(jax.jit(step2)(rebound, tok_sh))[..., : cfg.vocab_size]
    np.testing.assert_allclose(logits, outs[tps[0]], rtol=2e-4, atol=2e-4)
    print("weight_store: post-rebind serving matches")


def check_moe_sharded() -> None:
    from repro.models.moe import moe_apply_local, moe_apply_sharded, moe_param_defs

    cfg = reduced(get_config("moonshot-v1-16b-a3b"))
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    defs = moe_param_defs(cfg)
    params = init_params(defs, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)

    y_local = moe_apply_local(params, x, cfg)
    with mesh:  # Mesh-as-contextmanager works on old and new jax alike
        y_sh = jax.jit(lambda p, x: moe_apply_sharded(p, x, cfg, RULES, mesh))(params, x)
    np.testing.assert_allclose(
        np.asarray(y_sh), np.asarray(y_local), rtol=5e-4, atol=5e-4
    )
    print("moe_sharded: matches local oracle")


def check_migration() -> None:
    cfg = _tiny_cfg()
    devices = jax.devices()
    B, S = 8, 32
    # TP 1 -> 2: kv_exec stays 2 (head re-expansion for tp>kv is a separate
    # engine step); migration reshards heads across the new TP groups.
    ec_lo = make_exec_config(cfg, 1)
    mesh_lo = make_exec_mesh(devices, 1)
    cache_defs = init_cache_defs(cfg, ec_lo, B, S)
    cache = init_params(cache_defs, jax.random.PRNGKey(0), jnp.float32)
    # fill with recognizable contents
    cache = jax.tree_util.tree_map(
        lambda x: jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape), cache
    )
    sh_lo = cache_shardings(cache_defs, RULES, mesh_lo)
    cache_lo = jax.tree_util.tree_map(jax.device_put, cache, sh_lo)

    mesh_hi = make_exec_mesh(devices, 2)
    sh_hi = cache_shardings(cache_defs, RULES, mesh_hi)
    migrated, dt = migrate_cache(cache_lo, sh_hi)
    for a, b in zip(
        jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(migrated)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"migration: contents preserved across TP meshes ({dt*1e3:.2f} ms)")

    # every switch between TP levels stays on the devices: reading an
    # array's value to the host during migration fails the check. The cache
    # is laid out for the highest TP, as the engine lays it out.
    from jax._src.array import ArrayImpl

    def no_host_copy(self):
        raise AssertionError("migration copied a cache array through the host")

    cache_defs = init_cache_defs(cfg, make_exec_config(cfg, len(devices)), B, S)
    cache = jax.tree_util.tree_map(
        lambda d: jnp.arange(np.prod(d.shape), dtype=jnp.float32).reshape(d.shape),
        cache_defs, is_leaf=is_def,
    )
    cur = jax.tree_util.tree_map(
        jax.device_put, cache, cache_shardings(cache_defs, RULES, mesh_lo)
    )
    value = ArrayImpl._value
    ArrayImpl._value = property(no_host_copy)
    path = (4, 1, 2, 8, 1, 8, 4, 2, 1)
    try:
        for tp in path:
            sh = cache_shardings(cache_defs, RULES, make_exec_mesh(devices, tp))
            cur, _ = migrate_cache(cur, sh)
            assert all(x.sharding == s for x, s in zip(
                jax.tree_util.tree_leaves(cur), jax.tree_util.tree_leaves(sh)))
    finally:
        ArrayImpl._value = value
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(cur)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"migration: TP 1->{'->'.join(map(str, path))} without a host copy")


def check_fault_abort() -> None:
    from repro.core.migration import MigrationAborted

    cfg = _tiny_cfg()
    devices = jax.devices()
    canon_defs = model_param_defs(cfg, make_exec_config(cfg, 1))
    canonical = init_params(canon_defs, jax.random.PRNGKey(0), jnp.float32)
    store = WeightStore(cfg, canon_defs, RULES, devices, storage_tp=1)
    B, S = 8, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    def serve(store, storage, tp, mesh):
        sel = store.select_fn(tp, mesh)
        ec = make_exec_config(cfg, tp)

        def step(storage, tokens):
            params = sel(storage)
            h, _ = forward(params, cfg, ec, rules=RULES, mesh=mesh,
                           tokens=tokens, mode="prefill",
                           block_q=16, block_k=16)
            return logits_for(params, cfg, h, RULES, mesh)

        tok_sh = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
        return np.asarray(jax.jit(step)(storage, tok_sh))[..., : cfg.vocab_size]

    # reference logits at TP=1 on the full pool
    ref = serve(store, store.build(canonical, make_exec_mesh(devices, 1)),
                1, make_exec_mesh(devices, 1))

    # 1. migration whose target is invalid: original cache untouched
    ec_lo = make_exec_config(cfg, 1)
    cache_defs = init_cache_defs(cfg, ec_lo, B, 32)
    cache = init_params(cache_defs, jax.random.PRNGKey(2), jnp.float32)
    cache = jax.tree_util.tree_map(
        lambda x: jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape), cache
    )
    sh_lo = cache_shardings(cache_defs, RULES, make_exec_mesh(devices, 1))
    cache_lo = jax.tree_util.tree_map(jax.device_put, cache, sh_lo)
    bad_sh = jax.tree_util.tree_map(lambda _: object(), sh_lo)
    try:
        migrate_cache(cache_lo, bad_sh)
        raise AssertionError("migration did not abort")
    except MigrationAborted:
        pass
    for a, b in zip(
        jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(cache_lo)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("fault_abort: aborted migration left the source cache intact")

    # 2. weight reload on a shrunken pool (lost one 4-chip host)
    survivors = devices[: len(devices) // 2]
    small = store.shrink(survivors)
    assert small.N == len(survivors) and small.bytes_per_device() > 0
    mesh_small = make_exec_mesh(survivors, 2)
    reloaded = small.build(canonical, mesh_small)  # the reload storm
    np.testing.assert_allclose(
        serve(small, reloaded, 2, mesh_small), ref, rtol=2e-4, atol=2e-4,
    )
    print(f"fault_abort: reload on {small.N}-chip shrunken pool serves "
          "identical logits")


def _tiny_engine():
    """(a maker of float32 engines over every device at TP 1, 2 and 4, a
    maker of the same ten seeded requests)."""
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request

    cfg = ModelConfig(
        name="tiny-serve", family="dense", num_layers=2, d_model=64,
        num_heads=8, num_kv_heads=8, head_dim=16, d_ff=128, vocab_size=256,
        attn=AttnSpec(kind="full"),
    )
    defs = model_param_defs(cfg, make_exec_config(cfg, 1))
    params = init_params(defs, jax.random.PRNGKey(0), jnp.float32)
    econf = EngineConfig(
        candidate_tps=(1, 2, 4), n_slots=8, max_len=96,
        prefill_buckets=(16, 32), dtype=jnp.float32,
    )

    def mk_requests():
        rng = np.random.RandomState(0)
        return [
            Request(i, "strict", rng.randint(0, 256, size=rng.randint(4, 30)).astype(np.int32), 24)
            for i in range(10)
        ]

    return lambda: ServingEngine(cfg, params, jax.devices(), econf), mk_requests


def check_engine() -> None:
    """End-to-end: serving with mid-stream TP switches must produce the same
    greedy trajectories as a fixed-TP run (the switch is semantically
    invisible — the paper's correctness requirement for §3.2)."""
    mk_engine, mk_requests = _tiny_engine()
    eng_a = mk_engine()
    warm = eng_a.warmup()
    print(f"engine: warmed {len(eng_a.tps)} TP levels in {warm:.1f}s (offline)")
    done_a = eng_a.run(mk_requests())
    base = {r.req_id: list(r.generated) for r in done_a}

    eng_b = mk_engine()
    eng_b.warmup()
    done_b = eng_b.run(mk_requests(), switch_schedule={3: 2, 7: 4, 13: 1, 19: 2})
    assert len(eng_b.stats.switch_log) >= 3
    for r in done_b:
        assert base[r.req_id] == list(r.generated), (
            f"req {r.req_id}: trajectory changed across TP switches\n"
            f"base={base[r.req_id]}\ngot ={r.generated}"
        )
    log = eng_b.stats.switch_log
    print(
        f"engine: {len(done_b)} requests served across {len(log)} TP "
        f"switches; rebind {sum(s['rebind_s'] for s in log)*1e3:.2f} ms total, "
        f"migrate {sum(s['migrate_s'] for s in log)*1e3:.1f} ms total — "
        "trajectories identical"
    )


def check_engine_rollback() -> None:
    """A TP switch whose cache migration fails raises MigrationAborted and
    leaves the engine as it was: the same TP, storage, caches and switch
    log. Serving then goes on with the tokens of a run that never tried to
    switch, and a retry of the switch succeeds."""
    import repro.serving.engine as engine_mod
    from repro.core.migration import MigrationAborted

    mk_engine, mk_requests = _tiny_engine()
    base = {r.req_id: list(r.generated) for r in mk_engine().run(mk_requests())}

    eng = mk_engine()
    eng.warmup()
    real = engine_mod.migrate_cache

    def dying_migrate(cache, target):  # fails once, as a device lost mid-migration
        engine_mod.migrate_cache = real
        raise RuntimeError("device lost mid-migration")

    pending, done, step = mk_requests(), [], 0
    while pending or any(r is not None for r in eng.slot_req):
        if step == 3:
            before = (eng.tp, eng.storage, eng.slots.arrays, list(eng.stats.switch_log))
            engine_mod.migrate_cache = dying_migrate
            try:
                eng.switch_tp(2)
                raise AssertionError("switch_tp(2) did not abort")
            except MigrationAborted:
                pass
            finally:
                engine_mod.migrate_cache = real
            after = (eng.tp, eng.storage, eng.slots.arrays, eng.stats.switch_log)
            assert after[0] == before[0] == 1, after[0]
            assert after[1] is before[1] and after[2] is before[2], "storage or caches rebound"
            assert after[3] == before[3] == [], after[3]
        while pending and eng.slots.free:
            eng.admit(pending.pop(0))
        done.extend(eng.step())
        step += 1
    assert {r.req_id: list(r.generated) for r in done} == base, "tokens changed after the abort"
    print(f"engine_rollback: aborted switch TP 1->2 rolled back; {len(done)} requests "
          "served the tokens of a run that never switched")

    eng.switch_tp(2)  # retry once the fault has cleared
    assert eng.tp == 2 and [(s["from_tp"], s["to_tp"]) for s in eng.stats.switch_log] == [(1, 2)]
    again = {r.req_id: list(r.generated) for r in eng.run(mk_requests())}
    assert again == base, "tokens changed after the retried switch"
    print("engine_rollback: retry succeeded; TP 2 serves the same tokens")


CHECKS = {
    "weight_store": check_weight_store,
    "moe_sharded": check_moe_sharded,
    "migration": check_migration,
    "fault_abort": check_fault_abort,
    "engine": check_engine,
    "engine_rollback": check_engine_rollback,
}


def main() -> None:
    name = sys.argv[1]
    CHECKS[name]()
    print(f"OK {name}")


if __name__ == "__main__":
    main()
