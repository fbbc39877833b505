"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9,...]

Prints ``name,us_per_call,derived`` CSV rows; detailed payloads land in
benchmarks/results/*.json (consumed by EXPERIMENTS.md).

Failure contract: every registered module runs (one broken cell never
shadows the others' results), but any failure — import error or a raise
inside ``run()`` — is recorded, echoed as a ``FAILED`` CSV row, summarized
with its traceback on stderr at the end, and the process exits nonzero.
An unknown ``--only`` name is an immediate error, not a silent no-op.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "fig2_tp_properties",
    "fig3_static_vs_dynamic",
    "fig7_kv_migration",
    "fig9_goodput",
    "fig12_ablation",
    "fig13_14_slo",
    "fig15_scalability",
    "fig16_17_sensitivity",
    "sched_throughput",
    "fleet_throughput",
    "noisy_neighbor",
    "sim_throughput",
    "kv_backpressure",
    "scenario_matrix",
    "fault_matrix",
    "cascade_matrix",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    unknown = [m for m in mods if m not in MODULES]
    if unknown:
        raise SystemExit(
            f"unknown benchmark(s) {unknown}; registered: {MODULES}"
        )

    print("name,us_per_call,derived")
    failures = []  # (name, formatted traceback)
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            # one-line echo now; the end-of-run summary owns the traceback
            failures.append((name, traceback.format_exc()))
            print(f"{name},0,FAILED:{e!r}", flush=True)
            continue
        wall = (time.time() - t0) * 1e6
        for r in rows:
            if r.us_per_call == 0.0:
                r.us_per_call = wall / max(len(rows), 1)
            print(r.csv(), flush=True)
    if failures:
        print(
            f"\n=== {len(failures)}/{len(mods)} benchmark(s) FAILED ===",
            file=sys.stderr,
        )
        for name, tb in failures:
            print(f"\n--- {name} ---\n{tb}", file=sys.stderr)
        raise SystemExit(
            f"benchmarks failed: {[name for name, _ in failures]}"
        )


if __name__ == "__main__":
    main()
