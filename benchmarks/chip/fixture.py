"""A small benchmark tree for the CPU tests: a copy of this directory with a
tiny dense GQA configuration, two small mixes and a BENCHMARK.json naming
them, plus a metric file that no harness file names."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

TINY = {
    "name": "tiny", "architectures": ["MistralForCausalLM"],
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "sliding_window": 4096,
    "tie_word_embeddings": False, "reference": "dense_gqa",
    "serving": {"slots_per_chip": 4, "max_len": 128, "prefill_buckets": [32, 64],
                "dtype": "bfloat16"},
    "check": {"min_tokens": 120, "min_seqs": 6},
}
CLASSES = {"short": {"prompt": {"mean": 20, "sigma": 0.6, "lo": 4, "hi": 64},
                     "output": {"mean": 12, "sigma": 0.5, "lo": 2}}}
OPEN = {"arrivals": "open", "rate_rps": 12.0, "phase_s": 0.5, "drain_s": 20,
        "classes": CLASSES,
        "phases": [{"tp": 4, "mix": [{"class": "short", "tier": "strict", "share": 0.5},
                                     {"class": "short", "tier": "relaxed", "share": 0.5}]},
                   {"tp": 1, "mix": [{"class": "short", "tier": "strict", "share": 0.5},
                                     {"class": "short", "tier": "relaxed", "share": 0.5}]}],
        "tiers": {"strict": {"ttft_ms": 1e5, "tpot_ms": 1e5},
                  "relaxed": {"ttft_ms": 1e5, "tpot_ms": 1e5}}}
BATCH = {"arrivals": "batch", "batch_requests": 16, "classes": CLASSES,
         "phases": [{"tp": 1, "mix": [{"class": "short", "tier": "relaxed", "share": 1.0}]}]}
# the end-to-end metrics of a served, tiered cell
SERVED_E2E = [
    {"name": n, "unit": u, "better": b, "bound": 0.25, "source": "host_clock", "workloads": []}
    for n, u, b in (("ttft_p90_ms", "ms", "lower"), ("tpot_p90_ms", "ms", "lower"),
                    ("goodput_rps", "req/s", "higher"))
]
METRIC = '''"""Fixture metric: accepted admits in the window."""
import readers


def read(ctx):
    return len(readers.admits(ctx)) or None
'''


def make(tmp: Path, chips: int = 1) -> tuple:
    """(root, bench, spec) of a benchmark tree under `tmp`, with cells
    "tiny.open" (TPs 1 and 4 where chips == 4, else TP 1) and "tiny.batch"."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    mix = json.loads(json.dumps(OPEN))
    if chips == 1:
        for ph in mix["phases"]:
            ph["tp"] = 1
    (bench / "traffic" / "tiny-open.json").write_text(json.dumps(mix))
    (bench / "traffic" / "tiny-batch.json").write_text(json.dumps(BATCH))
    (bench / "metrics" / "fixture_admits.py").write_text(METRIC)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "fixture",
                            "file": "benchmarks/chip/configs/tiny.json", "reduced": [],
                            "why": "fixture"})
    spec["workloads"] += [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open", "chips": chips,
         "why": "fixture"},
        {"name": "tiny.batch", "config": "tiny", "traffic": "tiny-batch", "chips": 1,
         "why": "fixture"},
    ]
    # the entries a served cell adds, as a later PR would: the files they name exist
    have = {m["name"] for m in spec["end_to_end"]}
    spec["end_to_end"] += [dict(m, workloads=["tiny.open"]) for m in SERVED_E2E
                           if m["name"] not in have]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "tokens_per_s" or m["name"].endswith(".batch"):
            m["workloads"] = m["workloads"] + ["tiny.batch"]
    spec["per_layer"].append({"name": "fixture_admits", "unit": "1", "better": "higher",
                              "source": "program_counter", "layer": "prefill step",
                              "moves": "ttft_p90_ms", "workloads": ["tiny.open"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp, bench, spec


class no_persistent_cache:
    """Keeps a test's runs out of JAX's persistent compilation cache, and puts
    back the settings a run changes."""
    KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")

    def __enter__(self):
        import jax
        self.old = {k: getattr(jax.config, k) for k in self.KEYS}
        jax.config.update("jax_enable_compilation_cache", False)

    def __exit__(self, *exc):
        import jax
        for k, v in self.old.items():
            jax.config.update(k, v)


def run(tmp: Path, cell: str, seconds: float = 2.0, trace: bool = False, chips: int = 1,
        seed: int = 2**33 + 5, **kw) -> dict:
    """One run of a fixture cell on the CPU devices, past the look for a chip."""
    import time

    import jax

    import harness

    t0 = time.perf_counter()
    root, bench, spec = make(tmp, chips)
    cell = harness.cell_of(spec, cell)
    with no_persistent_cache():
        return harness.run_cell(spec, cell, seed, seconds, trace, jax.devices()[: cell["chips"]],
                                t0, root=root, bench=bench, **kw)
