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

The decode loop runs one step ahead of the host. A decode step returns its
next tokens on the device, in the shape and sharding it takes them, and they
are the next step's input without a trip through the host. `step` dispatches
step n+1 first, then reads step n's tokens to the host: while the host reads
and appends them, the device runs step n+1. The host owns the positions,
which advance at dispatch, and knows from them and each request's
`max_new_tokens` which step is a request's last; it releases the slot in the
call that dispatches that step, so `admit` may refill it at once (the insert
is ordered after the step in flight by its data dependency on the donated
caches). A request is returned by the call that reads its last token. A
read must come before the next dispatch (a drain) when `admit` puts a new
first token into the batch, when `switch_tp` moves to another mesh, and when
nothing is left to dispatch; the host's token vector is then whole, and the
next dispatch uploads it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.migration import MigrationAborted, cache_shardings, migrate_cache
from repro.core.weight_store import WeightStore, make_exec_mesh
from repro.models import forward, init_cache_defs, model_param_defs
from repro.models.model import logits_for
from repro.parallel.sharding import (
    DEFAULT_RULES, make_exec_config, shard_constraint, sharding_for,
)
from repro.serving.kv_cache import SlotCache
from repro.serving.request import Request, RequestState

# Host spans the engine opens (jax.profiler.TraceAnnotation, and
# StepTraceAnnotation for "engine.step"): they land in the profiler's trace on
# the device planes' clock. Identifiers (req_id, step_num, from_tp, to_tp) go
# in as trace stats, so the names stay fixed. Besides these, "engine.drain"
# wraps a read of the step in flight that must come before the next dispatch
# (an admit into a running batch, a TP switch, the end of the work); it is
# left out of SPANS, which the benchmark's recorded engine traces are checked
# against, and a traced run labels the device's idle time inside it.
SPANS = (
    "engine.admit", "engine.prefill", "engine.insert", "engine.first_token",
    "engine.step", "engine.decode", "engine.sync", "engine.logits", "engine.slots",
    "engine.switch", "engine.rebind", "engine.migrate",
)
# jax.named_scope names inside the compiled programs, read back from each
# device op's op_name: "select" (WeightStore.select_fn), "embed", "attention"
# with "kv_write" around cache updates, "mlp" (models/), "head" (the logits
# and argmax, here) and "migrate" (core/migration.migrate).
SCOPES = ("select", "embed", "attention", "kv_write", "mlp", "head", "migrate")
_span = jax.profiler.TraceAnnotation
TOKEN_AXES = ("batch",)  # a decode step's token input and output, (B, 1)


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
    steps: int = 0  # decode steps dispatched
    overlapped: int = 0  # steps dispatched while the previous step's tokens were unread
    drains: int = 0  # reads of the step in flight forced before a dispatch
    # one entry per executed switch: step, from_tp, to_tp, rebind_s, migrate_s
    switch_log: List[dict] = field(default_factory=list)


def make_decode_step(cfg: ModelConfig, store: WeightStore, tp: int, mesh,
                     cache_ec, rules=DEFAULT_RULES):
    """Jitted decode step at TP `tp` on `mesh`:
    (storage, caches, tokens (B,1), positions (B,)) -> (next (B,1), logits, caches).
    `next` is held to the sharding of TOKEN_AXES, which the engine gives
    `tokens`, so a step's output is the next step's input to the same
    executable. The caches are donated; their layout is fixed by `cache_ec`."""
    sel = store.select_fn(tp, mesh)

    def decode_step(storage, caches, tokens, positions):
        params = sel(storage)
        h, new_caches = forward(
            params, cfg, cache_ec, rules=rules, mesh=mesh, tokens=tokens,
            positions=positions, cache=caches, mode="decode",
        )
        with jax.named_scope("head"):
            logits = logits_for(params, cfg, h, rules, mesh)[:, 0, : cfg.vocab_size]
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            nxt = shard_constraint(nxt, TOKEN_AXES, rules, mesh)
        return nxt, logits, new_caches

    return jax.jit(decode_step, donate_argnums=(1,))


def make_prefill_step(cfg: ModelConfig, store: WeightStore, tp: int, mesh,
                      cache_ec, rules=DEFAULT_RULES):
    """Jitted one-request prefill at TP `tp` on `mesh`:
    (storage, tokens (1,L), true_len) -> (next, logits, sequence cache)."""
    sel = store.select_fn(tp, mesh)

    def prefill_step(storage, tokens, true_len):
        params = sel(storage)
        h, cache = forward(
            params, cfg, cache_ec, rules=rules, mesh=mesh, tokens=tokens,
            mode="prefill", block_q=64, block_k=64,
        )
        with jax.named_scope("head"):
            h_last = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
            logits = logits_for(params, cfg, h_last, rules, mesh)[:, 0, : cfg.vocab_size]
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, logits, cache

    return jax.jit(prefill_step)


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
        # slot -> the request whose next step is still to dispatch
        self.slot_req: List[Optional[Request]] = [None] * econf.n_slots
        # the next step's tokens on the host, whole after every read of the
        # step in flight; `_tokens` is the device copy, None until uploaded
        self.next_tokens = np.zeros(econf.n_slots, np.int32)
        self._tokens = None
        # the step dispatched and not yet read: (next, logits or None, [(slot, req, last)])
        self._inflight: Optional[tuple] = None
        self._finished: List[Request] = []  # read to the end, not yet returned
        self.stats = StepStats()
        self.logit_trace: Dict[int, list] = {}

        self._decode_fns = {
            tp: make_decode_step(cfg, self.store, tp, self.meshes[tp], cache_ec, rules)
            for tp in tps
        }
        self._prefill_fns = {  # one executable per bucket length, by shape
            tp: make_prefill_step(cfg, self.store, tp, self.meshes[tp], cache_ec, rules)
            for tp in tps
        }
        self._insert_fn = jax.jit(self.insert_cache, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _cache_shardings(self, tp: int):
        return cache_shardings(self.slots.cache_defs(), self.rules, self.meshes[tp])

    @staticmethod
    def insert_cache(caches, seq_cache, slot):
        """Write one request's prefill cache into slot `slot` of every layer."""
        def upd(c, s):
            idx = (jnp.zeros((), jnp.int32), slot) + tuple(
                jnp.zeros((), jnp.int32) for _ in range(c.ndim - 2)
            )
            return jax.lax.dynamic_update_slice(c, s.astype(c.dtype), idx)

        with jax.named_scope("kv_write"):
            return jax.tree_util.tree_map(upd, caches, seq_cache)

    # ------------------------------------------------------------------
    def warmup(self) -> float:
        """AOT-warm every (tp, stage) executable, and the cache resharding
        between consecutive candidate TPs — the paper's offline CUDA-graph
        capture. Each decode step is called as the loop calls it: from an
        uploaded token vector, then from a step's own output. Returns total
        compile seconds."""
        t0 = time.perf_counter()
        dummy_pos = np.zeros((self.econf.n_slots,), np.int32)
        cur = self.tp
        for tp in self.tps:
            self._rebind_and_migrate(tp)
            tokens = self._device_tokens()
            for _ in range(2):
                tokens, _, self.slots.arrays = self._decode_fns[tp](
                    self.storage, self.slots.arrays, tokens, dummy_pos
                )
            jax.block_until_ready(tokens)
            for L in self.econf.prefill_buckets:
                _, _, seq_cache = self._prefill_fns[tp](
                    self.storage, np.zeros((1, L), np.int32), 1
                )
                # slot 0 holds junk until its first admit overwrites it
                self.slots.arrays = self._insert_fn(self.slots.arrays, seq_cache, 0)
                jax.block_until_ready(self.slots.arrays)
        self._rebind_and_migrate(cur)
        self._tokens = None
        return time.perf_counter() - t0

    def _device_tokens(self):
        """The next step's tokens on the device: the last step's output, or
        the host's vector uploaded with the sharding that output has."""
        if self._tokens is None:
            self._tokens = jax.device_put(
                self.next_tokens.reshape(-1, 1),
                sharding_for(TOKEN_AXES, self.rules, self.meshes[self.tp]),
            )
        return self._tokens

    def _rebind_and_migrate(self, tp: int) -> Tuple[float, float]:
        """Move the engine to TP `tp`: rebind the weights to its mesh
        (zero-copy), then migrate the slot caches to its layout. Returns
        (rebind_s, migrate_s).

        Transactional: on any failure the previous weight binding is put back
        and `MigrationAborted` raised, with `tp` and the caches as they were.
        Rollback is free, since the rebind aliases the same per-device
        buffers and migration leaves its source intact."""
        if tp == self.tp:
            return 0.0, 0.0
        self._drain()
        self._tokens = None  # the next dispatch uploads them on the new mesh
        prev = self.storage
        try:
            t0 = time.perf_counter()
            with _span("engine.rebind"):
                self.storage = self.store.rebind(self.storage, self.meshes[tp])
            rebind_s = time.perf_counter() - t0
            with _span("engine.migrate"):
                arrays, migrate_s = migrate_cache(self.slots.arrays, self._cache_shardings(tp))
        except Exception as e:  # noqa: BLE001 - any failure leaves the old TP in place
            self.storage = prev
            if isinstance(e, MigrationAborted):
                raise
            raise MigrationAborted(f"switch TP {self.tp}->{tp} aborted: {e}") from e
        self.slots.arrays, self.tp = arrays, tp
        return rebind_s, migrate_s

    def switch_tp(self, tp: int) -> dict:
        """Stop-and-migrate TP switch (paper §3.2): a read of the step in
        flight, zero-copy weight rebind + one resharding program for all slot
        caches. Raises `MigrationAborted` with the engine still serving at
        its old TP."""
        if tp not in self.meshes:
            raise ValueError(
                f"switch_tp({tp}): no mesh for TP {tp}; this engine has TPs {self.tps}"
            )
        if tp == self.tp:
            return {"rebind_s": 0.0, "migrate_s": 0.0}
        from_tp = self.tp
        with _span("engine.switch", from_tp=from_tp, to_tp=tp):
            rebind_s, migrate_s = self._rebind_and_migrate(tp)
        self.stats.switch_log.append({
            "step": self.stats.steps, "from_tp": from_tp, "to_tp": tp,
            "rebind_s": rebind_s, "migrate_s": migrate_s,
        })
        return {"rebind_s": rebind_s, "migrate_s": migrate_s}

    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.econf.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds buckets")

    def admit(self, req: Request, now: float = 0.0) -> bool:
        with _span("engine.admit", req_id=req.req_id):
            return self._admit(req)

    def _admit(self, req: Request) -> bool:
        L = self._bucket(req.prompt_len)  # before alloc: a refused prompt holds no slot
        slot = self.slots.alloc()
        if slot is None:
            return False
        if req.arrival_s == 0.0:  # demo requests: arrival = admission
            req.arrival_s = time.perf_counter()
        tokens = np.zeros((1, L), np.int32)
        tokens[0, : req.prompt_len] = req.prompt
        with _span("engine.prefill"):
            nxt, logits, seq_cache = self._prefill_fns[self.tp](
                self.storage, tokens, req.prompt_len
            )
        with _span("engine.insert"):
            self.slots.arrays = self._insert_fn(self.slots.arrays, seq_cache, slot)
        self._drain()  # while the prefill runs: the host's tokens are whole after it
        with _span("engine.first_token"):
            tok = int(np.asarray(nxt)[0])
        req.slot = slot
        req.generated.append(tok)
        req.first_token_s = time.perf_counter()
        if self.econf.record_logits:
            self.logit_trace.setdefault(req.req_id, []).append(np.asarray(logits)[0])
        if req.done:  # one token asked for: no decode step
            self._finish(req, req.first_token_s)
            self.slots.release(slot)
            return True
        req.state = RequestState.DECODE
        self.slot_req[slot] = req
        self.slots.lengths[slot] = req.prompt_len
        self.next_tokens[slot] = tok
        self._tokens = None
        return True

    @property
    def busy(self) -> bool:
        """Whether `step` has work: a slot to decode, a step to read, or a
        finished request to return."""
        return (self._inflight is not None or bool(self._finished)
                or any(r is not None for r in self.slot_req))

    def step(self) -> List[Request]:
        """Dispatch the next decode iteration over all active slots, then
        read the previous one; returns the requests whose last token that
        read brought. With nothing to dispatch, only reads."""
        with jax.profiler.StepTraceAnnotation("engine.step", step_num=self.stats.steps):
            return self._step()

    def _step(self) -> List[Request]:
        if any(r is not None for r in self.slot_req):
            prev, self._inflight = self._inflight, None
            self._dispatch()
            if prev is not None:
                self.stats.overlapped += 1
                self._read(prev)
        else:
            self._drain()
        finished, self._finished = self._finished, []
        return finished

    def _dispatch(self) -> None:
        """Dispatch one decode step from the device tokens; advance the
        positions and release the slot of each request this step ends."""
        positions = self.slots.lengths.astype(np.int32)
        with _span("engine.decode"):
            nxt, logits, self.slots.arrays = self._decode_fns[self.tp](
                self.storage, self.slots.arrays, self._device_tokens(), positions
            )
        self._tokens = nxt
        self.stats.steps += 1
        rows = []
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            self.slots.lengths[slot] += 1
            length = int(self.slots.lengths[slot])
            # tokens once this step is read: the prefill's one, one per step
            last = (length - req.prompt_len + 1 >= req.max_new_tokens
                    or length + 1 >= self.econf.max_len)
            rows.append((slot, req, last))
            if last:
                self.slot_req[slot] = None
                self.slots.release(slot)
        self._inflight = (nxt, logits if self.econf.record_logits else None, rows)

    def _read(self, inflight: tuple) -> None:
        """Bring a dispatched step's tokens (and logits, when recording) to
        the host and append them to their requests."""
        nxt, logits, rows = inflight
        with _span("engine.sync"):
            nxt = np.asarray(nxt)[:, 0]
        with _span("engine.logits"):  # opened on every read, as SPANS says
            logits = None if logits is None else np.asarray(logits)
        with _span("engine.slots"):
            self.next_tokens[:] = nxt
            now = time.perf_counter()
            for slot, req, last in rows:
                req.generated.append(int(nxt[slot]))
                if logits is not None:
                    self.logit_trace[req.req_id].append(logits[slot])
                if last:
                    self._finish(req, now)

    def _drain(self) -> None:
        """Read the step in flight, where one is, before the next dispatch."""
        if self._inflight is None:
            return
        inflight, self._inflight = self._inflight, None
        with _span("engine.drain"):
            self._read(inflight)
        self.stats.drains += 1

    def _finish(self, req: Request, now: float) -> None:
        req.state = RequestState.DONE
        req.finish_s = now
        self._finished.append(req)

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
        while (pending or self.busy) and step_no < max_steps:
            if step_no in switch_schedule:
                self.switch_tp(switch_schedule[step_no])
            while pending and self.slots.free:
                self.admit(pending.pop(0))
            done.extend(self.step())
            step_no += 1
        return done
