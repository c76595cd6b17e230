"""Program spans (`core.spans`): the record and its parent links, the
planner's stages in order and what a cached plan skips, the solver's three
phases around its engine calls, the `grid_steps` count against the grid the
kernel really builds, and the names the executables carry on a trace."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    cg,
    clear_engine_cache,
    clear_schedule_cache,
    get_engine,
    jacobi,
    pagerank,
    power_iteration,
    spans,
)
from repro.core.matrices import hpcg_stencil, spd
from repro.kernels import sell_spmv

STAGES = ["planner.convert", "planner.digest", "planner.schedule",
          "planner.lower"]


def _names(record, prefix=""):
    return [s.name for s in record if s.name.startswith(prefix)]


def test_spans_nest_with_parent_links_and_counts():
    with spans.recording() as record:
        with spans.span("solver.cg"):
            with spans.span("engine.matvec", calls=1) as counts:
                counts["more"] = 2
            with spans.span("solver.cg.loop"):
                pass
    by_name = {s.name: s for s in record}
    # A span is recorded when it ends, so children come before parents.
    assert [s.name for s in record] == ["engine.matvec", "solver.cg.loop",
                                        "solver.cg"]
    assert by_name["engine.matvec"].parent == "solver.cg"
    assert by_name["solver.cg.loop"].parent == "solver.cg"
    assert by_name["solver.cg"].parent is None
    assert by_name["engine.matvec"].counts == {"calls": 1, "more": 2}
    outer, inner = by_name["solver.cg"], by_name["engine.matvec"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert outer.seconds >= inner.seconds >= 0


def test_nothing_is_recorded_outside_a_recording():
    with spans.span("engine.matvec") as counts:
        counts["x"] = 1
    with spans.recording() as record:
        pass
    assert record == []
    with spans.recording() as record:
        with pytest.raises(RuntimeError, match="already"):
            with spans.recording():
                pass
        with spans.span("engine.matvec"):
            pass
    with spans.span("engine.matmat"):
        pass
    assert _names(record) == ["engine.matvec"]


def test_span_always_enters_a_trace_annotation(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with spans.span("engine.matvec"):
        pass
    with spans.recording():
        with spans.span("solver.cg"):
            pass
    assert entered == ["engine.matvec", "solver.cg"]


def _tiny():
    return hpcg_stencil(4, 4, 4)()


def test_planner_stages_run_in_order_and_count_grid_steps():
    with spans.recording() as record:
        engine = get_engine(_tiny(), backend="pallas")
        engine.device_matvec()
    stages = _names(record, "planner.")
    assert set(stages) == set(STAGES)
    firsts = [stages.index(name) for name in STAGES]
    assert firsts == sorted(firsts)
    # One build: the schedule and the lowering run once, last.
    assert stages[-2:] == STAGES[-2:]
    assert all(s.parent == "planner" for s in record
               if s.name.startswith("planner."))
    lower = next(s for s in record if s.name == "planner.lower")
    plan = engine._device_plan
    assert lower.counts == {
        "grid_steps": sell_spmv.grid_steps(plan),
        "x_resident": 1,
        "block_rows": 128,
        "max_warps": plan.max_warps,
        "lane_gathers": sell_spmv.lane_gathers(plan)}
    # The stages do not overlap, and the planner spans hold them.
    planned = sum(s.seconds for s in record if s.name == "planner")
    assert sum(s.seconds for s in record
               if s.name.startswith("planner.")) <= planned


@pytest.mark.parametrize("hit", ["engine", "schedule_memory",
                                 "schedule_disk"])
def test_cached_plan_skips_the_stages_it_bypasses(hit, tmp_path):
    A = _tiny()
    kw = {"cache_dir": str(tmp_path)} if hit == "schedule_disk" else {}
    first = get_engine(A, backend="pallas", **kw)
    first.device_matvec()
    if hit != "engine":
        clear_engine_cache()
    if hit == "schedule_disk":
        clear_schedule_cache()
    with spans.recording() as record:
        engine = get_engine(A, backend="pallas", **kw)
        engine.device_matvec()
    assert (engine is first) == (hit == "engine")
    stages = _names(record, "planner.")
    assert "planner.schedule" not in stages
    assert ("planner.lower" in stages) == (hit != "engine")


SOLVERS = {
    "cg": lambda A, b: cg(A, b, maxiter=3),
    "jacobi": lambda A, b: jacobi(A, b, maxiter=3),
    "pagerank": lambda A, b: pagerank(A, maxiter=3),
    "power_iteration": lambda A, b: power_iteration(A, maxiter=3),
}


@pytest.mark.parametrize("solver", sorted(SOLVERS))
def test_solver_phases_inside_the_solver_span(solver):
    A = spd(64, 3)()
    b = np.ones(A.n_rows, np.float32)
    SOLVERS[solver](A, b)  # plan and compile outside the record
    with spans.recording() as record:
        SOLVERS[solver](A, b)
    top = f"solver.{solver}"
    phases = [f"{top}.{p}" for p in ("start", "loop", "result")]
    assert _names(record, top) == phases + [top]
    assert all(s.parent == top for s in record if s.name in phases)
    if solver == "cg":
        # The prologue's r = b - A x0 is an engine call.
        matvec = [s for s in record if s.name == "engine.matvec"]
        assert [s.parent for s in matvec] == ["solver.cg.start"]
    # A warm solve finds its engine again (the CSR is converted and hashed
    # for the lookup) and builds nothing.
    assert set(_names(record, "planner.")) == {"planner.convert",
                                               "planner.digest"}


def _kernel_steps(jaxpr) -> int:
    """Grid steps of every `pallas_call` in `jaxpr`, through `lax.map`'s scan,
    nested jits and the resident call's `sequential_vmap` wrapper."""
    steps = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            steps += math.prod(eqn.params["grid_mapping"].grid)
            continue
        for key in ("jaxpr", "call"):
            if key in eqn.params:
                inner = eqn.params[key]
                steps += eqn.params.get("length", 1) * _kernel_steps(
                    getattr(inner, "jaxpr", inner))
    return steps


@pytest.mark.parametrize("tag_budget", [sell_spmv.SMEM_TAG_BUDGET, 4096],
                         ids=["one_group", "slice_groups"])
def test_grid_steps_match_the_kernel_grid(tag_budget, monkeypatch):
    # The budget is read while the kernel is traced: no trace made under one
    # budget may serve this test or the tests after it.
    jax.clear_caches()
    monkeypatch.setattr(sell_spmv, "SMEM_TAG_BUDGET", tag_budget)
    try:
        A = hpcg_stencil(6, 6, 6)()
        with spans.recording() as record:
            engine = get_engine(A, backend="pallas")
            apply, ops = engine.device_matvec()
        jaxpr = jax.make_jaxpr(apply)(ops, jnp.ones(A.n_cols, jnp.float32))
        plan = engine._device_plan
        group = sell_spmv.slices_per_call(plan.n_slices, plan.n_chunks,
                                          plan.max_warps)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert (group < plan.n_slices) == (tag_budget == 4096)
    lower = next(s for s in record if s.name == "planner.lower")
    assert lower.counts["grid_steps"] == _kernel_steps(jaxpr.jaxpr) > 0


@pytest.mark.parametrize("x_budget", [None, 0], ids=["x_fits", "x_over"])
def test_x_resident_count_follows_the_engagement_rule(x_budget,
                                                      monkeypatch):
    """`x_resident` on `planner.lower` reads 1 where x fits the budget, and
    `grid_steps` then counts one step per tile of slices; with x over the
    budget it reads 0 and the per-warp grid runs. Both give A @ x."""
    jax.clear_caches()
    if x_budget is not None:
        monkeypatch.setattr(sell_spmv, "X_RESIDENT_BUDGET", x_budget)
    try:
        A = hpcg_stencil(8, 8, 8)()  # 16 slices: two tiles
        with spans.recording() as record:
            engine = get_engine(A, backend="pallas")
            apply, ops = engine.device_matvec()
        x = jnp.asarray(np.random.default_rng(0).standard_normal(A.n_cols),
                        jnp.float32)
        steps = _kernel_steps(jax.make_jaxpr(apply)(ops, x).jaxpr)
        y = engine.matvec(x)
    finally:
        monkeypatch.undo()
        clear_engine_cache()
        jax.clear_caches()
    lower = next(s for s in record if s.name == "planner.lower")
    plan = engine._device_plan
    resident = int(x_budget is None)
    assert lower.counts["x_resident"] == resident
    assert lower.counts["grid_steps"] == steps
    if resident:
        assert steps == -(-plan.n_slices // sell_spmv.TILE_SLICES) == 2
    else:
        assert steps == plan.n_slices * plan.n_chunks * plan.max_warps
    dense = np.zeros((A.n_rows, A.n_cols))
    for r in range(A.n_rows):
        lo, hi = A.indptr[r], A.indptr[r + 1]
        dense[r, A.indices[lo:hi]] = A.data[lo:hi]
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("x_budget", [None, 0], ids=["resident", "per_warp"])
def test_planner_lower_counts_the_lane_gathers(x_budget, monkeypatch):
    """`planner.lower` records the plan's `block_rows` and `max_warps`, and
    `lane_gathers`: windows x ceil(max_warps / warps per gather) on the
    resident path, 0 on the per-warp grid."""
    if x_budget is not None:
        monkeypatch.setattr(sell_spmv, "X_RESIDENT_BUDGET", x_budget)
    A = _tiny()
    with spans.recording() as record:
        engine = get_engine(A, backend="pallas")
        engine.device_matvec()
    plan = engine._device_plan
    sched = engine.schedule
    lower = next(s for s in record if s.name == "planner.lower")
    assert lower.counts["block_rows"] == sched.block_rows == (
        128 if x_budget is None else 8)
    assert lower.counts["max_warps"] == sched.max_warps == plan.max_warps
    # A window of 8 columns x 32 rows fills 2 of a register's 8 sublanes.
    rounds = -(-sched.max_warps // (8 // (plan.window // 128)))
    assert lower.counts["lane_gathers"] == (
        sched.n_windows * rounds if x_budget is None else 0)


def test_executables_carry_their_names():
    A = spd(64, 3)()
    engine = get_engine(A, backend="pallas")
    apply, ops = engine.device_matvec()
    x = jnp.ones(A.n_cols, jnp.float32)
    assert "@jit_engine_matvec" in apply.lower(ops, x).as_text()
    X = jnp.ones((A.n_cols, 2), jnp.float32)
    assert "@jit_engine_matmat" in engine._matmat.lower(ops, X).as_text()
    cg(engine, np.ones(A.n_rows, np.float32), maxiter=2)
    (entry,) = engine._solver_loop_cache.values()
    state = (x, x, x, jnp.float32(1), jnp.int32(0), jnp.float32(0),
             jnp.zeros(2))
    assert "@jit_cg_loop" in entry["while"].lower(ops, state).as_text()
