"""The engine's decode loop runs one step ahead of the host: `step` dispatches
step n+1 before it reads step n's tokens. On a tiny float32 engine (the
configuration of `multidev_checks._tiny_engine`, on one CPU device, 8 slots),
the pipelined loop serves the greedy tokens of requests served one at a time,
ends each request at exactly `max_new_tokens`, and counts how often the
pipeline engages."""
import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttnSpec, ModelConfig
from repro.models import model_param_defs
from repro.models.params import init_params
from repro.parallel.sharding import make_exec_config
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.request import Request, RequestState

CFG = ModelConfig(
    name="tiny-serve", family="dense", num_layers=2, d_model=64,
    num_heads=8, num_kv_heads=8, head_dim=16, d_ff=128, vocab_size=256,
    attn=AttnSpec(kind="full"),
)

MAX_NEW = (1, 2, 3, 5, 9, 2, 17, 1, 4, 12, 2, 7, 30, 3)  # 14 requests for 8 slots


def _requests(max_new=MAX_NEW, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(i, "strict", rng.randint(0, 256, size=rng.randint(4, 30)).astype(np.int32), m)
            for i, m in enumerate(max_new)]


@pytest.fixture(scope="module")
def params():
    defs = model_param_defs(CFG, make_exec_config(CFG, 1))
    return init_params(defs, jax.random.PRNGKey(0), jnp.float32)


def _engine(params, record_logits=False):
    econf = EngineConfig(candidate_tps=(1,), n_slots=8, max_len=96, prefill_buckets=(16, 32),
                         dtype=jnp.float32, record_logits=record_logits)
    eng = ServingEngine(CFG, params, jax.devices()[:1], econf)
    eng.warmup()
    return eng


class _Compiles:
    """Counts backend compiles while on."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def one_at_a_time(params):
    """Each request of `_requests()` served alone, with logits: {req_id: (tokens, logits)}."""
    eng = _engine(params, record_logits=True)
    out = {}
    for req in _requests():
        (done,) = eng.run([req])
        out[done.req_id] = (list(done.generated), np.stack(eng.logit_trace[done.req_id]))
    return out


def test_pipelined_batch_serves_the_tokens_of_requests_served_alone(params, one_at_a_time):
    eng = _engine(params)
    refills = []  # admits into a slot whose last step was still in flight
    admit = eng._admit

    def watched(req):
        inflight = eng._inflight[2] if eng._inflight else []
        free = eng.slots.free[0] if eng.slots.free else None
        if any(slot == free and last for slot, _, last in inflight):
            refills.append(free)
        return admit(req)

    rows = {}  # req_id -> decode rows dispatched for it
    dispatch = eng._dispatch

    def counted():
        dispatch()
        for _, req, _ in eng._inflight[2]:
            rows[req.req_id] = rows.get(req.req_id, 0) + 1

    eng._admit, eng._dispatch = watched, counted
    compiles = _Compiles()
    compiles.on = True
    done = eng.run(_requests())
    compiles.on = False
    assert compiles.n == 0, "a program compiled inside the loop"
    assert refills, "no slot was refilled while its last step was in flight"
    assert sorted(r.req_id for r in done) == list(range(len(MAX_NEW)))  # each returned once
    for r in done:
        assert len(r.generated) == r.max_new_tokens, r.req_id
        assert r.state is RequestState.DONE and r.finish_s >= r.first_token_s
        assert list(r.generated) == one_at_a_time[r.req_id][0], r.req_id
    assert not eng.busy and eng.slot_req == [None] * eng.econf.n_slots
    assert len(eng.slots.free) == eng.econf.n_slots
    # no decode row for a finished request: the prefill gives the first token
    assert rows == {i: m - 1 for i, m in enumerate(MAX_NEW) if m > 1}


def test_counters_count_overlapped_steps_and_drains(params):
    eng = _engine(params)
    reqs = _requests(max_new=(20, 20, 20, 20))
    for r in reqs[:3]:
        assert eng.admit(r)
    n = 5
    for _ in range(n):
        assert eng.step() == []
    assert (eng.stats.steps, eng.stats.overlapped, eng.stats.drains) == (n, n - 1, 0)
    assert [len(r.generated) for r in reqs[:3]] == [n] * 3  # the 5th step is still in flight
    assert eng.admit(reqs[3])  # into a running batch: reads the step in flight first
    assert eng.stats.drains == 1 and [len(r.generated) for r in reqs[:3]] == [n + 1] * 3
    eng.step()  # dispatched from tokens the host holds whole
    assert (eng.stats.steps, eng.stats.overlapped) == (n + 1, n - 1)
    eng.step()
    assert (eng.stats.steps, eng.stats.overlapped) == (n + 2, n)
    done = []
    while eng.busy:
        done += eng.step()
    assert sorted(r.req_id for r in done) == [0, 1, 2, 3]
    assert all(len(r.generated) == 20 for r in done)
    assert eng.stats.drains == 2  # the admit, and the end of the work
    assert eng.stats.steps == eng.stats.overlapped + 2  # the first, and the first after the admit
    assert eng.step() == [] and eng.stats.drains == 2  # nothing left: no read, no dispatch


def test_a_request_of_one_token_takes_no_decode_step(params):
    eng = _engine(params)
    (req,) = _requests(max_new=(1,))
    assert eng.admit(req)
    assert eng.slot_req == [None] * eng.econf.n_slots and len(eng.slots.free) == eng.econf.n_slots
    assert eng.step() == [req] and len(req.generated) == 1
    assert eng.stats.steps == 0 and not eng.busy


def test_recorded_logits_read_one_step_behind_match_requests_served_alone(params,
                                                                         one_at_a_time):
    eng = _engine(params, record_logits=True)
    done = eng.run(_requests())
    assert sorted(r.req_id for r in done) == list(range(len(MAX_NEW)))
    for r in done:
        got = np.stack(eng.logit_trace[r.req_id])
        assert got.shape == (len(r.generated), 256), r.req_id  # one row per token
        np.testing.assert_array_equal(got, one_at_a_time[r.req_id][1])


def test_a_request_stops_one_token_before_the_slot_is_full(params):
    eng = _engine(params)
    max_len = eng.econf.max_len
    (req,) = _requests(max_new=(max_len,))
    (done,) = eng.run([req])
    # the last token's row would be the slot's last position: it is never written
    assert len(done.generated) == max_len - req.prompt_len
