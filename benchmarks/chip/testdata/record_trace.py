#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction tests read.

    python3 benchmarks/chip/testdata/record_trace.py OUT.xplane.pb

On a TPU: runs the tiny benchmark tree's ``hpcg8.cg8`` cell (sets of 8 CG
iterations on the 8x8x8 HPCG stencil) through `harness.run_cell` with a
traced window of a few hundredths of a second, and keeps the window's
trace. The trace beside this file, ``v5e_cg_tiny.xplane.pb``, was recorded
so on one TPU v5e chip.
"""
from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "tests"))

WINDOW_S = 0.03


def main(out: str) -> int:
    import chipbench_tiny
    import jax

    from benchmarks.chip import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    harness.setup_process()
    with tempfile.TemporaryDirectory() as tmp:
        root = chipbench_tiny.make_root(Path(tmp))
        result = chipbench_tiny.run(root, "hpcg8.cg8", seconds=WINDOW_S,
                                    trace=True, trace_out=out)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
