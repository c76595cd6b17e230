#!/usr/bin/env python3
"""Bring-up smoke test of the SpMV engine and CG on one TPU chip.

    python chip_smoke.py               # one chip: HPCG CG, webbase-1M SpMV/SpMM
    python chip_smoke.py --four-chips  # four chips: sharded webbase-1M SpMM, SpMV

Runs in one process through the library's own entry points, at the full size
of two deployments built from the repository's generators:

* HPCG: unpreconditioned CG (`core.solvers.cg`, `lax.while_loop`) on the
  27-point stencil over the HPCG reference's 104^3 local domain, b = A @ 1.
  The true residual is recomputed in f64 on the host with scipy.
* webbase-1M: `SpMVEngine.matvec` and `matmat` at k = 8 on the 1M-row
  power-law matrix, each compared with scipy's f64 product.
* ``--four-chips``: `ShardedSpMVEngine` matmat at k = 8 and matvec on
  webbase-1M over a (data=4, model=1) mesh with the cost partition; each
  shard's result must sit on its own chip and no shard may fall back to the
  reference executor.

It fails (nonzero exit, no result line) unless JAX finds a TPU, the "auto"
backend resolves to compiled Pallas kernels, and every check passes. The
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
PARITY = 1e-5  # max |y - y_ref| <= PARITY * max |y_ref| (the repo's gate)
# Unpreconditioned CG needs ~0.95 iterations per grid point of the cube's
# edge to reach 1e-4 on this stencil in f32 (about 100 at 104^3); the cap
# leaves room and bounds the run time.
CG_TOL = 1e-4
CG_MAXITER = 200
K = 8

_compile_seconds = [0.0]
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_seconds[0] += seconds


def _timed(fn):
    """(result, wall seconds, seconds of it spent tracing and compiling)."""
    c0, t0 = _compile_seconds[0], time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, _compile_seconds[0] - c0


def _scipy(csr) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.asarray(csr.data, np.float64), csr.indices, csr.indptr),
        shape=(csr.n_rows, csr.n_cols),
    )


def _matrix(name: str):
    """The named deployment from the paper-scale suite (seeded)."""
    from repro.core.matrices import suite_specs

    spec = {s.name: s for s in suite_specs("paper")}[name]
    t0 = time.perf_counter()
    csr = spec.gen(np.random.default_rng(SEED))
    return csr, time.perf_counter() - t0


def _lower(eng) -> dict:
    """Plan `eng` for its matvec; the counts of its `planner.lower` span
    (`grid_steps`, `x_resident`, `block_rows`, `max_warps`,
    `lane_gathers`)."""
    from repro.core import spans

    with spans.recording() as record:
        eng.device_matvec()
    return next((s.counts for s in record if s.name == "planner.lower"), {})


def _geometry(eng, counts: dict) -> str:
    sched = eng.schedule
    n_slices = eng.sell.n_slices
    n_chunks = sched.n_windows // n_slices
    return (
        f"rows={eng.n_rows} nnz={int(np.count_nonzero(eng.sell.values))} "
        f"slices={n_slices} plan_width={n_chunks * eng.cols_per_chunk} "
        f"window={eng.window} windows={sched.n_windows} "
        f"block_rows={eng.block_rows} max_warps={sched.max_warps} "
        f"x_resident={counts.get('x_resident')} "
        f"grid_steps_per_spmv={counts.get('grid_steps')} "
        f"lane_gathers_per_spmv={counts.get('lane_gathers')}"
    )


def _parity(name: str, y, y_ref) -> bool:
    err = float(np.max(np.abs(np.asarray(y, np.float64) - y_ref)))
    limit = PARITY * float(np.max(np.abs(y_ref)))
    ok = bool(np.all(np.isfinite(y))) and err <= limit
    print(f"  {name}: max_err={err!r} limit={limit!r} ok={ok}", flush=True)
    return ok


def hpcg_phase(grid=(104, 104, 104), *, backend: str = "auto",
               tol: float = CG_TOL, maxiter: int = CG_MAXITER) -> bool:
    from repro.core.engine import get_engine, schedule_cache_stats
    from repro.core.matrices import hpcg_stencil
    from repro.core.solvers import cg

    print(f"[hpcg] 27-point stencil {grid}, CG tol={tol!r} "
          f"maxiter={maxiter} (iteration cap)", flush=True)
    if tuple(grid) == (104, 104, 104):
        csr, t_gen = _matrix("hpcg")
    else:
        t0 = time.perf_counter()
        csr = hpcg_stencil(*grid)(np.random.default_rng(SEED))
        t_gen = time.perf_counter() - t0
    A = _scipy(csr)
    b = (A @ np.ones(csr.n_cols)).astype(np.float32)
    builds0 = schedule_cache_stats()["built"]
    eng, t_plan, _ = _timed(lambda: get_engine(csr, backend=backend))
    counts, dt, _ = _timed(lambda: _lower(eng))  # schedule + device plan
    t_plan += dt
    print(f"  backend={eng.backend_resolved} {_geometry(eng, counts)}",
          flush=True)
    res, t_solve, t_compile = _timed(
        lambda: cg(eng, b, tol=tol, maxiter=maxiter)
    )
    builds = schedule_cache_stats()["built"] - builds0
    x = np.asarray(res.x, np.float64)
    true_res = float(
        np.linalg.norm(b.astype(np.float64) - A @ x)
        / np.linalg.norm(b.astype(np.float64))
    )
    ok = (
        res.converged and bool(np.all(np.isfinite(x))) and true_res <= tol
        and builds == 1 and res.schedule_builds == 0 and res.loop == "while"
    )
    print(
        f"  iterations={res.iterations} solver_residual={res.residual!r} "
        f"true_residual_f64={true_res!r} converged={res.converged} "
        f"schedule_builds={builds} (plan {builds - res.schedule_builds}, "
        f"solve {res.schedule_builds}) loop={res.loop}", flush=True,
    )
    print(
        f"  seconds: matrix={t_gen:.3f} plan={t_plan:.3f} "
        f"compile={t_compile:.3f} run={t_solve - t_compile:.3f} "
        f"ok={ok}", flush=True,
    )
    return ok


def webbase_phase(csr=None, *, backend: str = "auto", k: int = K) -> bool:
    from repro.core.engine import get_engine

    t_gen = 0.0
    if csr is None:
        csr, t_gen = _matrix("webbase-1M")
    print(f"[webbase] powerlaw n={csr.n_rows} nnz={csr.nnz}, matvec and "
          f"matmat k={k}", flush=True)
    A = _scipy(csr)
    rng = np.random.default_rng(SEED + 1)
    x = rng.standard_normal(csr.n_cols).astype(np.float32)
    X = rng.standard_normal((csr.n_cols, k)).astype(np.float32)
    eng, t_plan, _ = _timed(lambda: get_engine(csr, backend=backend))
    counts, dt, _ = _timed(lambda: _lower(eng))
    t_plan += dt
    print(f"  backend={eng.backend_resolved} {_geometry(eng, counts)}",
          flush=True)
    y, t_mv, c_mv = _timed(lambda: np.asarray(eng.matvec(x)))
    ok = _parity("matvec", y, A @ x.astype(np.float64))
    Y_ref = A @ X.astype(np.float64)
    Y, t_mm, c_mm = _timed(lambda: np.asarray(eng.matmat(X)))
    ok &= _parity(f"matmat k={k}", Y, Y_ref)
    print(
        f"  seconds: matrix={t_gen:.3f} plan={t_plan:.3f} "
        f"compile={c_mv + c_mm:.3f} run_matvec={t_mv - c_mv:.3f} "
        f"run_matmat={t_mm - c_mm:.3f} ok={ok}", flush=True,
    )
    return bool(ok)


def four_chip_phase(csr=None, *, backend: str = "auto", k: int = K) -> bool:
    import jax

    from repro.core.dist import ShardedSpMVEngine
    from repro.launch.mesh import make_host_mesh

    t_gen = 0.0
    if csr is None:
        csr, t_gen = _matrix("webbase-1M")
    print(f"[four-chips] sharded matvec and matmat k={k} on powerlaw "
          f"n={csr.n_rows} nnz={csr.nnz}, partition=cost", flush=True)
    A = _scipy(csr)
    rng = np.random.default_rng(SEED + 2)
    X = rng.standard_normal((csr.n_cols, k)).astype(np.float32)
    x = rng.standard_normal(csr.n_cols).astype(np.float32)
    mesh = make_host_mesh(model_axis=1)  # (data=4, model=1)

    def plan():
        eng = ShardedSpMVEngine(
            csr, mesh=mesh, partition="cost", backend=backend
        )
        return eng, [_lower(shard) for shard in eng.engines]

    (eng, counts), t_plan, _ = _timed(plan)
    print(f"  shards block_rows={eng.block_rows} x_resident="
          f"{[c.get('x_resident') for c in counts]} grid_steps_per_spmv="
          f"{[c.get('grid_steps') for c in counts]} lane_gathers_per_spmv="
          f"{[c.get('lane_gathers') for c in counts]}", flush=True)
    def run():
        pending = eng.dispatch(eng.stage(X))
        jax.block_until_ready(pending.blocks)
        return pending

    pending, t_run, t_compile = _timed(run)
    ok = True
    homes = []
    for i, row in enumerate(pending.blocks):
        home = eng.devices[i % eng.n_data, 0]
        where = set().union(*(b.devices() for b in row))
        homes.append(home)
        lo, hi = eng.row_ranges[i]
        print(f"  shard {i}: rows [{lo}, {hi}) nnz_padded="
              f"{eng.engines[i].sell.nnz_padded} on {sorted(map(str, where))} "
              f"expected {home}", flush=True)
        ok &= where == {home}
    ok &= len(set(homes)) == eng.n_shards == len(jax.devices())
    Y = eng.finalize(pending)
    recovery = eng.plan_report()["recovery"]
    print(f"  recovery={recovery}", flush=True)
    ok &= recovery["recovered"] == 0 and not recovery["events"]
    ok &= _parity(f"sharded matmat k={k}", Y, A @ X.astype(np.float64))
    y, t_mv, c_mv = _timed(lambda: eng.matvec(x))
    ok &= _parity("sharded matvec", y, A @ x.astype(np.float64))
    print(f"  seconds: matrix={t_gen:.3f} plan={t_plan:.3f} "
          f"compile={t_compile + c_mv:.3f} run={t_run - t_compile:.3f} "
          f"run_matvec={t_mv - c_mv:.3f} ok={ok}", flush=True)
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded webbase-1M matmat and matvec over four "
             "chips",
    )
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_BACKEND"):
        print("REPRO_BACKEND is set; the smoke test only runs the default "
              "backend", file=sys.stderr)
        return 2

    import jax

    from repro.core.engine import resolve_backend
    from repro.kernels.ops import resolve_interpret

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"needs {want} chips, found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    backend, interpret = resolve_backend("auto"), resolve_interpret()
    print(f"backend auto -> {backend}, pallas interpret={interpret}",
          flush=True)
    if backend != "pallas" or interpret:
        return 1
    if args.four_chips:
        ok = four_chip_phase()
    else:
        ok = webbase_phase() and hpcg_phase()
    if not ok:
        print("smoke checks failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
