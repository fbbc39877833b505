"""SPMD mini-cluster serving engine with runtime-adaptive TP.

This is the *real* execution path (as opposed to the calibrated simulator):
continuous batching over dense slot caches, AOT-warmed prefill/decode
executables per TP level (the paper's warm processes), zero-copy weight
rebinding and stop-and-migrate KV resharding on a TP switch.

The pool runs as one SPMD program per TP level: at TP t over N chips the
mesh is (data=N/t, model=t) — the data axis is the paper's "N/t independent
TP groups", executing in lockstep with per-group batches composed by the
scheduler. Greedy decoding keeps trajectories deterministic so integration
tests can assert that a mid-stream TP switch is semantically invisible.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.migration import cache_shardings, migrate_cache, reshard
from repro.core.weight_store import WeightStore, make_exec_mesh
from repro.models import forward, init_cache_defs, model_param_defs
from repro.models.model import logits_for
from repro.parallel.sharding import DEFAULT_RULES, make_exec_config
from repro.serving.kv_cache import SlotCache
from repro.serving.request import Request, RequestState


@dataclass
class EngineConfig:
    candidate_tps: Sequence[int] = (1, 2, 4, 8)
    n_slots: int = 16
    max_len: int = 2048
    prefill_buckets: Sequence[int] = (128, 512)
    dtype: object = jnp.bfloat16
    record_logits: bool = False


@dataclass
class StepStats:
    steps: int = 0
    compile_s: float = 0.0
    # one entry per executed switch: step, from_tp, to_tp, rebind_s, migrate_s
    switch_log: List[dict] = field(default_factory=list)


def make_decode_step(cfg: ModelConfig, store: WeightStore, tp: int, mesh,
                     cache_ec, rules=DEFAULT_RULES):
    """Jitted decode step at TP `tp` on `mesh`:
    (storage, caches, tokens (B,1), positions (B,)) -> (next, logits, caches).
    The caches are donated; their layout is fixed by `cache_ec`."""
    sel = store.select_fn(tp, mesh)

    def step(storage, caches, tokens, positions):
        params = sel(storage)
        h, new_caches, _ = forward(
            params, cfg, cache_ec, rules=rules, mesh=mesh, tokens=tokens,
            positions=positions, cache=caches, mode="decode",
        )
        logits = logits_for(params, cfg, h, rules, mesh)[:, 0, : cfg.vocab_size]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, logits, new_caches

    return jax.jit(step, donate_argnums=(1,))


def make_prefill_step(cfg: ModelConfig, store: WeightStore, tp: int, mesh,
                      cache_ec, rules=DEFAULT_RULES):
    """Jitted one-request prefill at TP `tp` on `mesh`:
    (storage, tokens (1,L), true_len) -> (next, logits, sequence cache)."""
    sel = store.select_fn(tp, mesh)

    def pre(storage, tokens, true_len):
        params = sel(storage)
        h, cache, _ = forward(
            params, cfg, cache_ec, rules=rules, mesh=mesh, tokens=tokens,
            mode="prefill", block_q=64, block_k=64,
        )
        h_last = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
        logits = logits_for(params, cfg, h_last, rules, mesh)[:, 0, : cfg.vocab_size]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, logits, cache

    return jax.jit(pre)


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        canonical_params,
        devices: Sequence,
        econf: EngineConfig = EngineConfig(),
        rules=DEFAULT_RULES,
    ):
        self.cfg = cfg
        self.econf = econf
        self.rules = rules
        self.devices = list(devices)
        tps = [t for t in econf.candidate_tps if t <= len(self.devices)]
        if cfg.num_kv_heads < max(tps):
            raise ValueError(
                f"{cfg.name}: {cfg.num_kv_heads} KV heads < max TP {max(tps)}; "
                "the engine keeps kv_exec constant across TP levels"
            )
        assert cfg.moe is None or cfg.moe.num_experts >= max(tps)
        self.tps = tps

        defs = model_param_defs(cfg, make_exec_config(cfg, 1))
        self.store = WeightStore(cfg, defs, rules, self.devices, storage_tp=1)
        self.meshes = {tp: make_exec_mesh(self.devices, tp) for tp in tps}
        self.tp = tps[0]
        self.storage = self.store.build(canonical_params, self.meshes[self.tp])

        cache_ec = make_exec_config(cfg, max(tps))  # layout fixed at max-TP kv_exec
        cache_defs = init_cache_defs(cfg, cache_ec, econf.n_slots, econf.max_len)
        self.slots = SlotCache.create(
            cfg, cache_ec, econf.n_slots, econf.max_len, econf.dtype,
            cache_shardings(cache_defs, rules, self.meshes[self.tp]),
        )
        self.slot_req: List[Optional[Request]] = [None] * econf.n_slots
        self.next_tokens = np.zeros(econf.n_slots, np.int32)
        self.stats = StepStats()
        self.logit_trace: Dict[int, list] = {}

        t0 = time.perf_counter()
        self._decode_fns = {
            tp: make_decode_step(cfg, self.store, tp, self.meshes[tp], cache_ec, rules)
            for tp in tps
        }
        self._prefill_fns = {  # one executable per bucket length, by shape
            tp: make_prefill_step(cfg, self.store, tp, self.meshes[tp], cache_ec, rules)
            for tp in tps
        }
        self._insert_fn = jax.jit(self._insert, donate_argnums=(0,))
        self.stats.compile_s = time.perf_counter() - t0

    # ------------------------------------------------------------------
    def _cache_shardings(self, tp: int):
        return cache_shardings(self.slots.cache_defs(), self.rules, self.meshes[tp])

    @staticmethod
    def _insert(caches, seq_cache, slot):
        def upd(c, s):
            idx = (jnp.zeros((), jnp.int32), slot) + tuple(
                jnp.zeros((), jnp.int32) for _ in range(c.ndim - 2)
            )
            return jax.lax.dynamic_update_slice(c, s.astype(c.dtype), idx)

        return jax.tree_util.tree_map(upd, caches, seq_cache)

    # ------------------------------------------------------------------
    def warmup(self) -> float:
        """AOT-warm every (tp, stage) executable, and the cache resharding
        between consecutive candidate TPs — the paper's offline CUDA-graph
        capture. Returns total compile seconds."""
        t0 = time.perf_counter()
        dummy_tok = np.zeros((self.econf.n_slots, 1), np.int32)
        dummy_pos = np.zeros((self.econf.n_slots,), np.int32)
        cur = self.tp
        for tp in self.tps:
            self._switch_mesh_only(tp)
            nxt, _, self.slots.arrays = self._decode_fns[tp](
                self.storage, self.slots.arrays, dummy_tok, dummy_pos
            )
            jax.block_until_ready(nxt)
            for L in self.econf.prefill_buckets:
                _, _, seq_cache = self._prefill_fns[tp](
                    self.storage, np.zeros((1, L), np.int32), 1
                )
                # slot 0 holds junk until its first admit overwrites it
                self.slots.arrays = self._insert_fn(self.slots.arrays, seq_cache, 0)
                jax.block_until_ready(self.slots.arrays)
        self._switch_mesh_only(cur)
        dt = time.perf_counter() - t0
        self.stats.compile_s += dt
        return dt

    def _switch_mesh_only(self, tp: int) -> None:
        if tp == self.tp:
            return
        self.storage = self.store.rebind(self.storage, self.meshes[tp])
        self.slots.arrays = reshard(self.slots.arrays, self._cache_shardings(tp))
        self.tp = tp

    def switch_tp(self, tp: int) -> dict:
        """Stop-and-migrate TP switch (paper §3.2): zero-copy weight rebind +
        one resharding program for all slot caches."""
        if tp not in self.meshes:
            raise ValueError(
                f"switch_tp({tp}): no mesh for TP {tp}; this engine has TPs {self.tps}"
            )
        if tp == self.tp:
            return {"rebind_s": 0.0, "migrate_s": 0.0}
        t0 = time.perf_counter()
        self.storage = self.store.rebind(self.storage, self.meshes[tp])
        rebind_s = time.perf_counter() - t0
        self.slots.arrays, migrate_s = migrate_cache(
            self.slots.arrays, self._cache_shardings(tp)
        )
        self.stats.switch_log.append({
            "step": self.stats.steps, "from_tp": self.tp, "to_tp": tp,
            "rebind_s": rebind_s, "migrate_s": migrate_s,
        })
        self.tp = tp
        return {"rebind_s": rebind_s, "migrate_s": migrate_s}

    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.econf.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds buckets")

    def admit(self, req: Request, now: float = 0.0) -> bool:
        slot = self.slots.alloc()
        if slot is None:
            return False
        if req.arrival_s == 0.0:  # demo requests: arrival = admission
            req.arrival_s = time.perf_counter()
        L = self._bucket(req.prompt_len)
        tokens = np.zeros((1, L), np.int32)
        tokens[0, : req.prompt_len] = req.prompt
        nxt, logits, seq_cache = self._prefill_fns[self.tp](
            self.storage, tokens, req.prompt_len
        )
        self.slots.arrays = self._insert_fn(self.slots.arrays, seq_cache, slot)
        tok = int(np.asarray(nxt)[0])
        req.slot = slot
        req.state = RequestState.DECODE
        req.generated.append(tok)
        req.first_token_s = time.perf_counter()
        self.slot_req[slot] = req
        self.slots.lengths[slot] = req.prompt_len
        self.next_tokens[slot] = tok
        if self.econf.record_logits:
            self.logit_trace.setdefault(req.req_id, []).append(np.asarray(logits)[0])
        return True

    def step(self) -> List[Request]:
        """One decode iteration over all active slots; returns finished."""
        tokens = self.next_tokens.reshape(-1, 1)
        positions = self.slots.lengths.astype(np.int32)
        nxt, logits, self.slots.arrays = self._decode_fns[self.tp](
            self.storage, self.slots.arrays, tokens, positions
        )
        nxt = np.asarray(nxt)
        logits = np.asarray(logits)
        self.stats.steps += 1
        finished = []
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            self.slots.lengths[slot] += 1
            tok = int(nxt[slot])
            req.generated.append(tok)
            self.next_tokens[slot] = tok
            if self.econf.record_logits:
                self.logit_trace[req.req_id].append(logits[slot])
            if req.done or self.slots.lengths[slot] + 1 >= self.econf.max_len:
                req.state = RequestState.DONE
                req.finish_s = time.perf_counter()
                finished.append(req)
                self.slot_req[slot] = None
                self.slots.release(slot)
        return finished

    def run(
        self,
        requests: List[Request],
        switch_schedule: Optional[Dict[int, int]] = None,
        max_steps: int = 10_000,
    ) -> List[Request]:
        """Serve `requests` to completion; optionally switch TP at given
        step numbers ({step: tp})."""
        switch_schedule = switch_schedule or {}
        pending = list(requests)
        done: List[Request] = []
        step_no = 0
        while (pending or any(r is not None for r in self.slot_req)) and step_no < max_steps:
            if step_no in switch_schedule:
                self.switch_tp(switch_schedule[step_no])
            while pending and self.slots.free:
                self.admit(pending.pop(0))
            done.extend(self.step())
            step_no += 1
        return done
