"""One run of one cell: build, plan, warm up, measure, check, report.

Everything particular to a configuration, a traffic mix, an operation or a
metric lives in a file of its own, found by the name `BENCHMARK.json` gives:

- ``configs/<config>.json`` (the path is the config's ``file``): which
  generator under ``generators/`` builds the matrix, and its parameters;
- ``traffic/<traffic>.json``: the operation under ``ops/`` and its
  parameters, and the limit of each number the comparison reads;
- ``ops/<op>.py``: class ``Op`` (inputs from the seed, warm-up, one timed
  call, the work of a call, the comparison with the reference);
- ``metrics/<metric>.py``: ``read(run)`` returning the metric's value from
  a `Run`, or None where the run has nothing to read;
- ``peaks.json``: the device's peak rates, keyed by `device_kind`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import List, Optional

import numpy as np

from benchmarks.chip import tracing
from benchmarks.chip.sparse import Matrix
from benchmarks.chip.work import Work

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = COMPILE_EVENTS[-1]
# Variables of the program that would pin a knob or reuse a plan across
# processes; each run measures the defaults and plans from scratch.
PROGRAM_ENV = ("REPRO_BACKEND", "REPRO_SCHEDULE_CACHE")


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers here."""

    setup_s: float
    plan_s: float
    compile_s: float
    window_s: float
    calls: int
    work: Work  # of the calls completed in the window
    peak_bytes_per_s: float
    peak_flops_per_s: float
    trace: Optional[tracing.TraceSummary] = None


class CompileClock:
    """Seconds and count of JAX tracing/lowering/compiling, from
    `jax.monitoring` duration events."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def __call__(self, event: str, seconds: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += seconds
            self.backend_compiles += event == BACKEND_COMPILE


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, name: str):
    """(workload entry, config entry) of the cell `name`."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def metrics_of(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks_for(bench_dir: Path, device_kind: str) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peak rates for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def build_matrix(root: Path, bench_dir: Path, config: dict,
                 seed: int) -> Matrix:
    cfg = load_json(root / config["file"])
    gen = load_module(bench_dir / "generators" / f"{cfg['generator']}.py",
                      f"chipbench_gen_{cfg['generator']}")
    return gen.build(cfg["params"], seed)


def make_op(bench_dir: Path, traffic: dict, engine, matrix: Matrix,
            seed: int):
    mod = load_module(bench_dir / "ops" / f"{traffic['op']}.py",
                      f"chipbench_op_{traffic['op']}")
    return mod.Op(engine, matrix, traffic, seed)


def plan_engine(matrix: Matrix):
    """The program's engine for `matrix` at its defaults, planned and with
    its matvec compiled."""
    from repro.core.engine import get_engine
    from repro.core.formats import CSRMatrix

    csr = CSRMatrix(n_rows=matrix.n_rows, n_cols=matrix.n_cols,
                    indptr=matrix.indptr, indices=matrix.indices,
                    data=matrix.data)
    engine = get_engine(csr)
    engine.device_matvec()
    return engine


def free_program_state() -> None:
    from repro.core.engine import clear_engine_cache, clear_schedule_cache

    clear_engine_cache()
    clear_schedule_cache()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(root: Path, bench_dir: Path, spec: dict, cell_name: str, *,
             seed: int, seconds: float, trace: bool, t0: float,
             device, trace_out: Optional[str] = None) -> dict:
    """Run cell `cell_name` once on `device` and return the result object.
    `t0` is the `time.perf_counter()` reading at process start. With
    `trace`, the window's profiler trace is also copied to `trace_out`
    where that is given."""
    import jax

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        return _run(root, bench_dir, spec, cell_name, seed=seed,
                    seconds=seconds, trace=trace, t0=t0, device=device,
                    clock=clock, trace_out=trace_out)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def setup_process() -> str:
    """Process-wide set-up of a benchmark run; returns the compile cache
    directory. The program's knobs stay at their defaults and no plan is
    reused across processes; every program, however quick to compile, goes
    to the persistent compile cache, so only the first run of a cell in a
    checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    for var in PROGRAM_ENV:
        os.environ.pop(var, None)
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def _run(root, bench_dir, spec, cell_name, *, seed, seconds, trace, t0,
         device, clock, trace_out) -> dict:
    import jax

    cell, config = find_cell(spec, cell_name)
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    peaks = peaks_for(bench_dir, device.device_kind)
    log(f"cell {cell_name} seed {seed} on {device.platform} "
        f"{device.device_kind}")

    t = time.perf_counter()
    matrix = build_matrix(root, bench_dir, config, seed)
    t_matrix = time.perf_counter() - t
    c0, t = clock.seconds, time.perf_counter()
    engine = plan_engine(matrix)
    plan_wall = time.perf_counter() - t
    plan_compile = clock.seconds - c0
    op = make_op(bench_dir, traffic, engine, matrix, seed)
    t = time.perf_counter()
    op.warm()
    t_warm = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s: matrix {t_matrix:.3f} (rows {matrix.n_rows}"
        f" nnz {matrix.nnz}), plan {plan_wall:.3f} (of it compile "
        f"{plan_compile:.3f}), warm-up {t_warm:.3f}, compile in all "
        f"{clock.seconds:.3f}; backend {engine.backend_resolved}")

    outputs, call_s, failed_calls = [], [], 0
    compiles0 = clock.backend_compiles
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        start = time.perf_counter()
        deadline, i = start + seconds, 0
        while time.perf_counter() < deadline:
            t_call = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(tracing.CALL_SPAN):
                    outputs.append((i, op.call(i)))
                call_s.append(time.perf_counter() - t_call)
            except Exception:  # a failed call is counted, not fatal
                traceback.print_exc()
                failed_calls += 1
            i += 1
        window_s = time.perf_counter() - start
    summary = None
    if trace:
        jax.profiler.stop_trace()
        xplane = tracing.find_xplane(trace_dir)
        if trace_out:
            shutil.copyfile(xplane, trace_out)
        summary = tracing.summarize(tracing.load(xplane))
        shutil.rmtree(trace_dir)
    window_compiles = clock.backend_compiles - compiles0
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window {window_s:.3f} s: {i} calls, {failed_calls} failed, "
        f"{window_compiles} compiles; seconds per call "
        f"{[round(c, 4) for c in call_s]}")

    host = [(j, np.asarray(y)) for j, y in outputs]
    del outputs, engine
    op.engine = op.inputs = None
    free_program_state()
    limits = traffic["limits"]
    worst = {name: 0.0 for name in limits}
    failed = failed_calls
    t = time.perf_counter()
    for j, y in host:
        readings = op.compare(y, j)
        failed += any(not readings[k] <= limits[k] for k in readings)
        for k, v in readings.items():
            if not v <= worst[k]:  # a NaN reading sticks
                worst[k] = v
    log(f"reference and comparison {time.perf_counter() - t:.3f} s")

    run = Run(
        setup_s=setup_s, plan_s=plan_wall - plan_compile,
        compile_s=clock.seconds, window_s=window_s, calls=len(host),
        work=op.work() * len(host),
        peak_bytes_per_s=float(peaks["hbm_bytes_per_s"]),
        peak_flops_per_s=float(peaks["flops_per_s"]),
        trace=summary,
    )
    metrics = {}
    for m in metrics_of(spec, cell_name, trace):
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                             f"chipbench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = failed == 0 and len(host) > 0 and all(
        worst[k] <= limits[k] for k in limits)
    result = {
        "correct": correct,
        "attempted": i,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak,
        },
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                        for k in limits}
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return result

