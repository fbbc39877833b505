"""What decides `correct`, rehearsed on the CPU at reduced width: the engine's
served tokens pass against the float32 reference, the float8 control fails,
the reference imports nothing of the program, and a run with its timed path
broken underneath comes out not correct, for each fault a serving cell can
have."""
import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import check
import fixture

HERE = Path(__file__).resolve().parent
MID = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4, vocab_size=4096)


@pytest.fixture
def mid(monkeypatch):
    monkeypatch.setattr(fixture, "TINY", dict(fixture.TINY, **MID))


@pytest.fixture
def long_batch(monkeypatch, mid):
    """Hundreds of served tokens compared, as in a chip run: 256-token slots,
    outputs ~60."""
    cfg = dict(fixture.TINY, serving=dict(fixture.TINY["serving"], max_len=256,
                                          prefill_buckets=[64, 128]),
               check={"min_tokens": 400, "min_seqs": 8})
    monkeypatch.setattr(fixture, "TINY", cfg)
    short = {"prompt": {"mean": 40, "sigma": 0.6, "lo": 4, "hi": 128},
             "output": {"mean": 60, "sigma": 0.5, "lo": 2}}
    monkeypatch.setattr(fixture, "BATCH", dict(fixture.BATCH, classes={"short": short}))


def test_reference_imports_nothing_of_the_program():
    files = list((HERE / "reference").glob("*.py")) + [HERE / f for f in
                                                       ("weights.py", "counts.py", "check.py")]
    for f in files:
        for node in ast.walk(ast.parse(f.read_text())):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.split(".")[0] == "repro" for n in names), (f.name, names)


def test_engine_passes_and_float8_control_fails(tmp_path, long_batch):
    for seed in (3, 2**41 + 9):
        res = fixture.run(tmp_path / str(seed), "tiny.batch", seconds=6.0, seed=seed,
                          control=True)
        gap = res["check"]["widest_logit_gap"]["value"]
        assert res["check"]["tokens_compared"]["value"] >= 300
        assert res["correct"] is True and gap <= check.GAP_LIMIT
        assert res["control"]["widest_logit_gap"] > check.GAP_LIMIT
        assert res["control"]["correct"] is False


def _alter_tokens(eng):
    """A token altered where it is produced: the decode step's argmax + 1."""
    vocab = eng.cfg.vocab_size
    for tp, f in list(eng._decode_fns.items()):
        def g(storage, caches, tokens, positions, f=f):
            nxt, logits, caches = f(storage, caches, tokens, positions)
            return (nxt + 1) % vocab, logits, caches
        eng._decode_fns[tp] = g


def _skip_insert(eng):
    """A step that returns its state unchanged: the prefill's cache is never
    written into the slot."""
    eng._insert_fn = lambda caches, seq_cache, slot: caches


@pytest.mark.parametrize("fault", [_alter_tokens, _skip_insert], ids=["token", "state"])
def test_a_broken_timed_path_is_not_correct(tmp_path, mid, fault):
    res = fixture.run(tmp_path, "tiny.open", hooks=fault)
    assert res["correct"] is False
    assert res["check"]["widest_logit_gap"]["value"] > check.GAP_LIMIT


FOUR = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {here!r})
import fixture
fixture.TINY.update({mid!r})
import jax, jax.numpy as jnp
import repro.serving.engine as engine

def skip_exchange(eng):
    def migrate(cache, target):  # the new layout's buffers, never filled from the old
        return jax.tree_util.tree_map(
            lambda a, s: jnp.zeros(a.shape, a.dtype, device=s), cache, target), 0.0
    engine.migrate_cache = migrate

out = {{}}
for name, hook in (("sound", None), ("no_exchange", skip_exchange)):
    res = fixture.run(Path(tempfile.mkdtemp()), "tiny.open", seconds=2.5, chips=4, hooks=hook)
    out[name] = [res["correct"], res["check"]["widest_logit_gap"]["value"],
                 res["device"]["count"]]
print(json.dumps(out))
"""


def test_the_exchange_between_chips_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(fixture.ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join([src, env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", FOUR.format(here=str(HERE), mid=MID)],
                         capture_output=True, text=True, env=env, timeout=600, cwd=fixture.ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"][0] is True and res["sound"][2] == 4
    assert res["no_exchange"][0] is False and res["no_exchange"][1] > check.GAP_LIMIT


def test_sample_holds_the_longest_and_one_request_spanning_each_switch():
    from types import SimpleNamespace as NS

    import numpy as np

    reqs = [NS(req_id=i, prompt_len=10 + i, generated=[0] * (5 + i), prompt=np.zeros(10 + i))
            for i in range(12)]
    recs = {i: NS(first_s=float(i), last_s=float(i) + 1.5) for i in range(12)}
    got = check.sample(reqs, recs, switch_s=[3.2, 7.9], seed=2**45, min_tokens=10, min_seqs=4)
    ids = [r.req_id for r in got]
    assert ids[0] == 11  # the longest
    assert ids[1] in (2, 3) and ids[2] in (6, 7)  # decoding across 3.2 and 7.9
    assert len(ids) == 4  # 10 served tokens were reached at 3; drawn on to 4 requests
    again = check.sample(reqs, recs, switch_s=[3.2, 7.9], seed=2**45, min_tokens=10, min_seqs=4)
    assert [r.req_id for r in again] == ids
    assert check.sample([], recs, [], 1, 10, 4) == []
