#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload hpcg104.cg --seed 7 \
        --seconds 10 --trace 0

Builds the cell's matrix and inputs from the seed, plans the program's
engine at its defaults, warms up, runs the cell's operation back to back
for ``--seconds`` (the window closes when the call in flight at the
deadline completes), then checks every answer of the window against the
plain reference. ``--trace 1`` records a profiler trace of the window and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from benchmarks.chip import harness

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell, _ = harness.find_cell(spec, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"cell needs {cell['chips']} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    harness.log(f"compile cache {harness.setup_process()}")
    result = harness.run_cell(
        ROOT, BENCH_DIR, spec, args.workload, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t0=T0,
        device=devices[0],
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
