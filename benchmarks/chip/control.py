"""Readings that set the limit of `correct`: the program's widest logit gap
and the float8 control's, over many seeds, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Each seed is a full run of the cell at its own load and sizes (weights from
the seed, the window, the drain) followed by the check, which reads the
float32 reference's verdict on the served tokens (the program's reading) and
then, on the same prompts and tokens, the gap of the token that a float8
reference puts first (the control's reading). The benchmark's own runs never
compute the control. Prints one JSON line per seed and a summary last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness

    spec = harness.load_spec(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.cell_of(spec, args.workload)
    import jax

    devices = jax.devices()[: cell["chips"]]
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control.py: needs the cell's TPU chips", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    rows = []
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        r = harness.run_cell(spec, cell, int(s), args.seconds, False, devices, t0, control=True)
        rows.append({"seed": int(s), "correct": r["correct"],
                     "program": r["check"]["widest_logit_gap"]["value"],
                     "control": r.get("control", {}).get("widest_logit_gap"),
                     "control_correct": r.get("control", {}).get("correct"),
                     "tokens": r["check"]["tokens_compared"]["value"],
                     "metrics": {k: v["value"] for k, v in r["metrics"].items()}})
        print(json.dumps(rows[-1]), flush=True)
    prog = [r["program"] for r in rows if r["program"] is not None]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"lower_reading": max(prog) if prog else None,
                      "upper_reading": min(ctrl) if ctrl else None,
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
