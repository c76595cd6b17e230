"""A benchmark tree at tiny sizes, for running the harness on the CPU.

`make_root(tmp)` copies the benchmark directory (its code, traffic limits
and metric readers) into ``tmp/benchmarks/chip`` and writes beside it a
`BENCHMARK.json` whose cells run the real operations on small matrices,
with a peak table entry for the CPU.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

CONFIGS = {
    "hpcg8": {"generator": "hpcg_stencil",
              "params": {"nx": 8, "ny": 8, "nz": 8}},
}
# The real CG mix with its set shortened to what 512 rows need, and a
# fused k = 8 product mix, which no cell of the benchmark runs yet, so
# that `ops/spmm.py` is driven end to end.
TRAFFIC = {"cg8": {"op": "cg", "iterations": 8, "rhs": 2},
           "spmm8": {"op": "spmm", "k": 8, "inputs": 2,
                     "limits": {"matmat": 1e-5}}}
CELLS = [("hpcg8.cg8", "hpcg8", "cg8"), ("hpcg8.spmv", "hpcg8", "spmv"),
         ("hpcg8.spmm8", "hpcg8", "spmm8")]


def load(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "testdata", "__pycache__"))
    for name, cfg in CONFIGS.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, traffic in TRAFFIC.items():
        traffic = {"limits": load("cg")["limits"], **traffic}
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = {"hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [c for c, _, _ in CELLS]
    spec["configs"] = [
        {"name": n, "source": "test", "why": "test", "reduced": [],
         "file": f"benchmarks/chip/configs/{n}.json"} for n in CONFIGS]
    spec["workloads"] = [
        {"name": c, "config": cfg, "traffic": t, "chips": 1, "why": "test"}
        for c, cfg, t in CELLS]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = names
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return tmp


def run(root: Path, cell: str, *, seed: int = 3, seconds: float = 0.2,
        trace: bool = False, trace_out=None) -> dict:
    import time

    import jax

    from benchmarks.chip import harness

    spec = json.loads((root / "BENCHMARK.json").read_text())
    return harness.run_cell(
        root, root / "benchmarks" / "chip", spec, cell, seed=seed,
        seconds=seconds, trace=trace, t0=time.perf_counter(),
        device=jax.devices()[0], trace_out=trace_out)
