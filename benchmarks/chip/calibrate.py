#!/usr/bin/env python3
"""Readings that the comparison limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload hpcg104.cg \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 4 --out OUT.json

In one process, for each seed: builds the cell's matrix and inputs as a
run with that seed does, drives the program's timed call on the first
``--inputs`` inputs, and reads each compared number against the float64
reference (the lower reading is the largest over the seeds). For the first
``--control-seeds`` seeds it also puts the bfloat16 control in the
program's place and reads the same numbers (the upper reading is the
smallest). Benchmark runs never run this; it needs a TPU like they do.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--inputs", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from benchmarks.chip import harness

    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    harness.setup_process()
    from repro.core.engine import clear_engine_cache

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell, config = harness.find_cell(spec, args.workload)
    traffic = harness.load_json(BENCH_DIR / "traffic" /
                                f"{cell['traffic']}.json")
    rows, digest = [], None
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        matrix = harness.build_matrix(ROOT, BENCH_DIR, config, seed)
        if matrix.digest() != digest:  # new values: plan a new engine
            clear_engine_cache()  # (the schedule cache is kept)
            digest = matrix.digest()
        op = harness.make_op(BENCH_DIR, traffic, harness.plan_engine(matrix),
                             matrix, seed)
        op.warm()
        t_setup = time.perf_counter() - t
        for i in range(args.inputs):
            t = time.perf_counter()
            out = op.call(i)
            t_call = time.perf_counter() - t
            row = {"seed": seed, "input": i, "setup_s": t_setup,
                   "call_s": t_call,
                   "program": op.compare(jax.device_get(out), i)}
            del out
            if n < args.control_seeds:
                row["control"] = op.compare(op.expected(i, "bfloat16"), i)
            rows.append(row)
            harness.log(json.dumps(row))
    checks = sorted(rows[0]["program"])
    summary = {
        k: {
            "limit": traffic["limits"][k],
            "program_max": max(r["program"][k] for r in rows),
            "control_min": min((r["control"][k] for r in rows
                                if "control" in r), default=None),
        } for k in checks
    }
    result = {"workload": args.workload,
              "device": jax.devices()[0].device_kind,
              "summary": summary, "rows": rows}
    Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
