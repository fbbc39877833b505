"""Chip benchmark for Nitsum's serving engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. Set-up (weights made on the device from the seed, warm-up of the
cell's programs from the persistent compile cache) is timed as `setup_s`;
then the window is served open-loop for `--seconds`, and the requests due in
it are drained. With `--trace 0` the last stdout line carries the cell's
end-to-end metrics; with `--trace 1`, the per-layer metrics read from a
profiler trace of the window. Either way the served tokens of a seeded sample
of requests are checked against the float32 reference, and the numbers
compared are printed, with their limits, as the last lines on stderr.

Exits nonzero, printing no result, where JAX finds no TPU, fewer chips than
the cell asks for, or a `device_kind` without published peaks.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1, also write the trace's events (gzipped JSON) here")
    args = ap.parse_args(argv)

    import harness
    from peaks import UnknownDevice, peaks_for

    spec = harness.load_spec(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.cell_of(spec, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    result = harness.run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                              devices[: cell["chips"]], T_START, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
