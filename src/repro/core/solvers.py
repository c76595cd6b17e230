"""Device-resident iterative solvers over the plan-once SpMV engines.

The paper's plan-once/execute-many design pays off when one coalescing
schedule is reused thousands of times; the classic consumers of SpMV are
exactly that shape. Each solver here runs its whole iteration *inside*
`jax.lax.while_loop`: the engine's hoisted `DevicePlan` (schedule tags /
warp maps) enters the loop as arguments of the jitted driver —
loop-invariant state — so per iteration there are zero host round-trips,
zero re-plans, and the convergence check (`rr > tol2`, L1 delta, ...) is
evaluated on device.

Three loop drivers, selected by ``loop=``:

- ``"while"`` — `jax.lax.while_loop` around the shared step function
  (default whenever the executor exposes `device_matvec`, i.e.
  `SpMVEngine` on either backend).
- ``"python"`` — an eager host loop over the *same* jitted cond/step
  functions. This is the bit-identity oracle: on the reference backend
  `while` and `python` produce bitwise-equal iterates (same traced body,
  same compiled arithmetic), which `tests/test_solvers.py` pins.
- ``"host"`` — a numpy-driven loop through `Executor.matvec`, for
  executors whose matvec is not jit-traceable (`ShardedSpMVEngine`,
  `StreamingExecutor`). Sharded CG reduces its dot products over the mesh
  ``data`` axis: `ShardedSpMVEngine.matvec_parts` hands back each shard's
  slice of ``A@p`` still on its own device, the partial ``<p, A p>`` runs
  there, and only scalars meet on the host.

Every solve reports `schedule_builds` — the delta of the global
plan-build counter across the solve — so callers (and the benchmark
gate) can assert the schedule was built exactly once regardless of
iteration count.

Each call runs in a ``solver.<name>`` span (`core.spans`); on the device
loops it holds ``solver.<name>.start`` (the eager prologue that builds the
loop state), ``solver.<name>.loop`` (dispatch of the jitted runner) and
``solver.<name>.result`` (the reads back to the host).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .engine import get_engine, schedule_cache_stats
from .formats import CSRMatrix, SELLMatrix, coo_to_csr
from .spans import span

__all__ = [
    "SolveResult",
    "cg",
    "jacobi",
    "pagerank",
    "power_iteration",
    "transition_matrix",
]

_LOOPS = ("auto", "while", "python", "host")


@dataclasses.dataclass
class SolveResult:
    """Outcome of an iterative solve.

    ``residual`` is the solver's own convergence metric at exit: relative
    2-norm residual ||b - Ax|| / ||b|| for cg/jacobi, L1 iterate delta for
    pagerank, relative eigen-residual ||Ax - lam x|| / |lam| for
    power_iteration. ``schedule_builds`` counts coalescing-schedule builds
    observed during this solve (plan-reuse proof: 1 cold, 0 warm).
    """

    x: np.ndarray
    iterations: int
    residual: float
    converged: bool
    solver: str
    loop: str
    schedule_builds: int
    residual_trace: Optional[np.ndarray] = None
    eigenvalue: Optional[float] = None


# --------------------------------------------------------------------------
# Operator / loop resolution


def _resolve_operator(A, *, backend: str, engine_kw: dict):
    if isinstance(A, (CSRMatrix, SELLMatrix)):
        return get_engine(A, backend=backend, **engine_kw)
    if callable(getattr(A, "matvec", None)):
        if backend != "auto" or engine_kw:
            opts = [f"backend={backend!r}"] if backend != "auto" else []
            opts += [f"{k}=..." for k in engine_kw]
            raise ValueError(
                f"{', '.join(opts)} cannot be applied to a prebuilt "
                f"{type(A).__name__} — it already fixes the backend and "
                f"engine options; pass the matrix instead, or drop the "
                f"engine arguments"
            )
        return A
    raise TypeError(
        f"expected a CSRMatrix/SELLMatrix or an Executor with .matvec, got "
        f"{type(A).__name__}"
    )


def _default_dtype() -> np.dtype:
    """JAX's default real dtype (f32, or f64 under jax_enable_x64) — the
    single source for both the device and host loop drivers, so loop='host'
    and loop='while' agree in precision."""
    return np.dtype(jnp.zeros(0).dtype)


def _resolve_loop(loop: str, ex) -> str:
    if loop not in _LOOPS:
        raise ValueError(f"loop must be one of {_LOOPS}, got {loop!r}")
    has_device = callable(getattr(ex, "device_matvec", None))
    if loop == "auto":
        return "while" if has_device else "host"
    if loop in ("while", "python") and not has_device:
        raise ValueError(
            f"loop={loop!r} needs a device-resident matvec "
            f"({type(ex).__name__} does not expose device_matvec) — use "
            f"loop='host'"
        )
    return loop


def _require_square(ex, solver: str) -> int:
    if ex.n_rows != ex.n_cols:
        raise ValueError(
            f"{solver} requires a square operator, got "
            f"{ex.n_rows}x{ex.n_cols}"
        )
    return int(ex.n_rows)


def _loop_runners(ex, key, cond, step):
    """Jitted while-runner + cond/step for the python oracle, cached per
    executor so repeat solves (same solver/maxiter/dtype) retrace nothing.
    The cache rides on the executor instance, which also owns the matvec
    and its plan arrays — their lifetimes match by construction.

    ``step(mv, s)`` receives the matvec bound to the plan arrays, which
    enter the jitted runners as arguments (`SpMVEngine.device_matvec`), not
    as compiled-in constants.

    Invariant: cond/step may only close over values that are constant for
    the executor's lifetime (maxiter, n). Anything that can differ between
    calls sharing a cache key — b, tolerances, damping — must flow through
    the loop state, or a warm solve replays the first call's value as a
    baked-in jit constant."""
    cache = ex.__dict__.setdefault("_solver_loop_cache", {})
    entry = cache.get(key)
    if entry is None:
        apply, ops = ex.device_matvec()

        def bound_step(ops, s):
            return step(lambda v: apply(ops, v), s)

        def run_loop(ops, s):
            return jax.lax.while_loop(cond, lambda s: bound_step(ops, s), s)

        # A profile's module line then reads jit_cg_loop and so on.
        run_loop.__name__ = f"{key[0]}_loop"
        entry = {
            "ops": ops,
            "while": jax.jit(run_loop),
            "cond": jax.jit(cond),
            "step": jax.jit(bound_step),
        }
        cache[key] = entry
    return entry


def _drive(entry, state, loop: str):
    ops = entry["ops"]
    if loop == "while":
        return entry["while"](ops, state)
    cond_j, step_j = entry["cond"], entry["step"]
    while bool(cond_j(state)):
        state = step_j(ops, state)
    return state


def _trace_out(tr, iterations: int, want: bool) -> Optional[np.ndarray]:
    if not want:
        return None
    return np.asarray(tr)[:iterations]


def _device_solve(solver, ex, mode, maxiter, start, cond, step, finish):
    """One solve on a device loop, in its three spans: ``start()`` builds
    the loop state eagerly and returns it with what ``finish`` needs
    besides; the jitted runner (cached per solver, `maxiter` and dtype of
    the iterate, the state's first entry) runs `cond`/`step`; ``finish(state,
    aux)`` reads the result back into a `SolveResult`."""
    with span(f"solver.{solver}.start"):
        state, aux = start()
    with span(f"solver.{solver}.loop"):
        key = (solver, maxiter, str(state[0].dtype))
        state = _drive(_loop_runners(ex, key, cond, step), state, mode)
    with span(f"solver.{solver}.result"):
        return finish(state, aux)


class _Solve:
    """One public solver call: its ``solver.<name>`` span, and the delta of
    the global schedule-build counter across it."""

    def __init__(self, solver: str):
        self._span = span(f"solver.{solver}")

    def __enter__(self):
        self._span.__enter__()
        self._before = schedule_cache_stats()["built"]
        return self

    def __exit__(self, *exc):
        self.builds = schedule_cache_stats()["built"] - self._before
        return self._span.__exit__(*exc)


# --------------------------------------------------------------------------
# Conjugate gradient


def cg(
    A,
    b,
    *,
    tol: float = 1e-6,
    maxiter: Optional[int] = None,
    x0=None,
    trace: bool = False,
    loop: str = "auto",
    backend: str = "auto",
    **engine_kw,
) -> SolveResult:
    """Conjugate gradient for SPD ``A`` (not verified — caller's contract;
    `core.matrices.make_spd` / `core.matrices.spd` produce valid inputs).
    Converges when ||r||_2 <= tol * ||b||_2, capped at ``maxiter``
    (default n) iterations."""
    with _Solve("cg") as bc:
        ex = _resolve_operator(A, backend=backend, engine_kw=engine_kw)
        n = _require_square(ex, "cg")
        mode = _resolve_loop(loop, ex)
        mi = n if maxiter is None else int(maxiter)
        if mode == "host":
            res = _cg_host(ex, b, tol=tol, maxiter=mi, x0=x0, trace=trace)
        else:
            res = _cg_device(
                ex, b, tol=tol, maxiter=mi, x0=x0, trace=trace, mode=mode
            )
    res.schedule_builds = bc.builds
    return res


def _cg_device(ex, b, *, tol, maxiter, x0, trace, mode) -> SolveResult:
    def start():
        b_ = jnp.asarray(b)
        x = jnp.zeros_like(b_) if x0 is None else jnp.asarray(x0, b_.dtype)
        bb = jnp.vdot(b_, b_)
        r = b_ - ex.matvec(x)
        rr = jnp.vdot(r, r)
        tol2 = jnp.asarray(tol, bb.dtype) ** 2 * bb
        tr = jnp.zeros((maxiter,), b_.dtype)
        return (x, r, r, rr, jnp.asarray(0, jnp.int32), tol2, tr), bb

    def cond(s):
        _x, _r, _p, rr, k, tol2, _tr = s
        return (k < maxiter) & (rr > tol2)

    def step(mv, s):
        x, r, p, rr, k, tol2, tr = s
        Ap = mv(p)
        alpha = rr / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rr_new = jnp.vdot(r, r)
        p = r + (rr_new / rr) * p
        tr = tr.at[k].set(jnp.sqrt(rr_new))
        return (x, r, p, rr_new, k + 1, tol2, tr)

    def finish(state, bb):
        x, r, p, rr, k, tol2, tr = state
        iters = int(k)
        bb_f = float(bb)
        resid = math.sqrt(float(rr)) / math.sqrt(bb_f) if bb_f > 0 else 0.0
        return SolveResult(
            x=x,
            iterations=iters,
            residual=resid,
            converged=bool(float(rr) <= float(tol2)),
            solver="cg",
            loop=mode,
            schedule_builds=0,
            residual_trace=_trace_out(tr, iters, trace),
        )

    return _device_solve("cg", ex, mode, maxiter, start, cond, step, finish)


def _host_matvec_and_dot(ex) -> Callable[[np.ndarray], Tuple[np.ndarray, float]]:
    """p -> (A@p as host array, <p, A@p>). On a ShardedSpMVEngine the dot
    is reduced over the mesh data axis: each shard's partial runs on its
    own device against its committed copy of p."""
    parts_fn = getattr(ex, "matvec_parts", None)
    if parts_fn is None:
        def mv_dot(p: np.ndarray):
            Ap = np.asarray(ex.matvec(jnp.asarray(p)))
            return Ap, float(np.dot(p, Ap))
        return mv_dot

    def mv_dot_sharded(p: np.ndarray):
        parts = parts_fn(jnp.asarray(p))
        partials = [
            jnp.vdot(placed[lo:hi], part) for part, placed, (lo, hi) in parts
        ]  # each partial computed on its shard's device
        Ap = np.concatenate([np.asarray(part) for part, _, _ in parts])
        return Ap, float(sum(float(d) for d in partials))

    return mv_dot_sharded


def _cg_host(ex, b, *, tol, maxiter, x0, trace) -> SolveResult:
    b = np.asarray(b)
    x = np.zeros_like(b) if x0 is None else np.asarray(x0, b.dtype)
    mv_dot = _host_matvec_and_dot(ex)
    bb = float(np.dot(b, b))
    r = b - np.asarray(ex.matvec(jnp.asarray(x)))
    p = r.copy()
    rr = float(np.dot(r, r))
    tol2 = tol * tol * bb
    tr: List[float] = []
    k = 0
    while k < maxiter and rr > tol2:
        Ap, pAp = mv_dot(p)
        alpha = rr / pAp
        x = x + alpha * p
        r = r - alpha * Ap
        rr_new = float(np.dot(r, r))
        p = r + (rr_new / rr) * p
        rr = rr_new
        tr.append(math.sqrt(rr))
        k += 1
    resid = math.sqrt(rr) / math.sqrt(bb) if bb > 0 else 0.0
    return SolveResult(
        x=x,
        iterations=k,
        residual=resid,
        converged=rr <= tol2,
        solver="cg",
        loop="host",
        schedule_builds=0,
        residual_trace=np.asarray(tr, b.dtype) if trace else None,
    )


# --------------------------------------------------------------------------
# Jacobi


def _diag_of(A_or_ex) -> np.ndarray:
    """Main diagonal as a host array, from CSR, SELL, or an executor that
    carries its SELL (`SpMVEngine.sell`, `ShardedSpMVEngine.sell`)."""
    obj = A_or_ex
    if not isinstance(obj, (CSRMatrix, SELLMatrix)):
        obj = getattr(obj, "sell", None)
        if obj is None:
            raise TypeError(
                f"cannot extract a diagonal from {type(A_or_ex).__name__}; "
                f"pass diag= explicitly"
            )
    if isinstance(obj, CSRMatrix):
        n = obj.n_rows
        row_of = np.repeat(np.arange(n), np.diff(obj.indptr))
        on_diag = obj.indices == row_of
        d = np.zeros(n, dtype=np.float64)
        np.add.at(d, row_of[on_diag], obj.data[on_diag])
        return d
    sell = obj
    H = sell.slice_height
    d = np.zeros(sell.n_slices * H, dtype=np.float64)
    for s in range(sell.n_slices):
        ci, va = sell.slice_arrays(s)
        rows = s * H + np.arange(ci.shape[1])
        d[rows] = (va * (ci == rows[None, :])).sum(axis=0)
    return d[: sell.n_rows]


def jacobi(
    A,
    b,
    *,
    tol: float = 1e-6,
    maxiter: int = 1000,
    omega: float = 1.0,
    diag=None,
    x0=None,
    trace: bool = False,
    loop: str = "auto",
    backend: str = "auto",
    **engine_kw,
) -> SolveResult:
    """(Weighted) Jacobi: x += omega * D^-1 (b - A x). Converges for
    strictly diagonally dominant A (`core.matrices.spd`). The residual in
    the trace/result is that of the iterate *entering* each step (one
    extra half-step of progress is already applied when the loop exits —
    checking after the update would cost a second matvec per iteration)."""
    with _Solve("jacobi") as bc:
        ex = _resolve_operator(A, backend=backend, engine_kw=engine_kw)
        n = _require_square(ex, "jacobi")
        mode = _resolve_loop(loop, ex)
        d = _diag_of(A) if diag is None else np.asarray(diag, np.float64)
        if d.shape != (n,):
            raise ValueError(f"diag must have shape ({n},), got {d.shape}")
        if (d == 0).any():
            raise ValueError("jacobi needs a nowhere-zero diagonal")
        inv_d = omega / d
        if mode == "host":
            res = _jacobi_host(
                ex, b, inv_d=inv_d, tol=tol, maxiter=int(maxiter), x0=x0,
                trace=trace,
            )
        else:
            res = _jacobi_device(
                ex, b, inv_d=inv_d, tol=tol, maxiter=int(maxiter), x0=x0,
                trace=trace, mode=mode,
            )
    res.schedule_builds = bc.builds
    return res


def _jacobi_device(ex, b, *, inv_d, tol, maxiter, x0, trace,
                   mode) -> SolveResult:
    def start():
        b_ = jnp.asarray(b)
        x = jnp.zeros_like(b_) if x0 is None else jnp.asarray(x0, b_.dtype)
        bb = jnp.vdot(b_, b_)
        tol2 = jnp.asarray(tol, bb.dtype) ** 2 * bb
        inv_dj = jnp.asarray(inv_d, b_.dtype)
        tr = jnp.zeros((maxiter,), b_.dtype)
        # b rides in the loop state (not the closure): the jitted cond/step
        # are cached per executor keyed only on (solver, maxiter, dtype),
        # and a closure-captured b would be baked into the compiled step as
        # a constant — a warm-engine solve with a different RHS would
        # silently solve the *first* system.
        state = (
            x, b_, jnp.asarray(jnp.inf, b_.dtype), jnp.asarray(0, jnp.int32),
            inv_dj, tol2, tr,
        )
        return state, bb

    def cond(s):
        _x, _b, rr, k, _inv_d, tol2, _tr = s
        return (k < maxiter) & (rr > tol2)

    def step(mv, s):
        x, b, _rr, k, inv_d, tol2, tr = s
        r = b - mv(x)
        rr = jnp.vdot(r, r)
        x = x + inv_d * r
        tr = tr.at[k].set(jnp.sqrt(rr))
        return (x, b, rr, k + 1, inv_d, tol2, tr)

    def finish(state, bb):
        x, _b, rr, k, _, tol2, tr = state
        iters = int(k)
        bb_f = float(bb)
        rr_f = float(rr) if np.isfinite(float(rr)) else float("inf")
        resid = math.sqrt(rr_f) / math.sqrt(bb_f) if bb_f > 0 else 0.0
        return SolveResult(
            x=x,
            iterations=iters,
            residual=resid,
            converged=bool(float(rr) <= float(tol2)),
            solver="jacobi",
            loop=mode,
            schedule_builds=0,
            residual_trace=_trace_out(tr, iters, trace),
        )

    return _device_solve(
        "jacobi", ex, mode, maxiter, start, cond, step, finish
    )


def _jacobi_host(ex, b, *, inv_d, tol, maxiter, x0, trace) -> SolveResult:
    b = np.asarray(b)
    x = np.zeros_like(b) if x0 is None else np.asarray(x0, b.dtype)
    inv_d = np.asarray(inv_d, b.dtype)
    bb = float(np.dot(b, b))
    tol2 = tol * tol * bb
    rr = float("inf")
    tr: List[float] = []
    k = 0
    while k < maxiter and rr > tol2:
        r = b - np.asarray(ex.matvec(jnp.asarray(x)))
        rr = float(np.dot(r, r))
        x = x + inv_d * r
        tr.append(math.sqrt(rr))
        k += 1
    resid = math.sqrt(rr) / math.sqrt(bb) if bb > 0 else 0.0
    return SolveResult(
        x=x,
        iterations=k,
        residual=resid,
        converged=rr <= tol2,
        solver="jacobi",
        loop="host",
        schedule_builds=0,
        residual_trace=np.asarray(tr, b.dtype) if trace else None,
    )


# --------------------------------------------------------------------------
# PageRank


def transition_matrix(adj: CSRMatrix) -> CSRMatrix:
    """Column-stochastic PageRank operator M = P^T from a (square) adjacency
    matrix: M[j, i] = 1/outdeg(i) for each stored edge i -> j (stored-entry
    multiplicity counts; values are ignored — the generators' random values
    are not edge weights). Columns of dangling nodes (outdeg 0) are all
    zero; the iteration's mass-conservation correction redistributes their
    rank uniformly, the standard dangling-node treatment."""
    if adj.n_rows != adj.n_cols:
        raise ValueError(
            f"transition_matrix needs a square adjacency, got "
            f"{adj.n_rows}x{adj.n_cols}"
        )
    n = adj.n_rows
    outdeg = np.diff(adj.indptr)
    row_of = np.repeat(np.arange(n), outdeg)
    vals = 1.0 / outdeg[row_of]
    return coo_to_csr(
        n, n, adj.indices.astype(np.int64), row_of.astype(np.int64), vals
    )


def pagerank(
    A,
    *,
    damping: float = 0.85,
    tol: float = 1e-8,
    maxiter: int = 200,
    x0=None,
    trace: bool = False,
    loop: str = "auto",
    backend: str = "auto",
    **engine_kw,
) -> SolveResult:
    """PageRank by power iteration on the transition operator. ``A`` is
    either an adjacency `CSRMatrix` (the transition matrix is built here)
    or an executor already wrapping `transition_matrix(adj)`. Each step is
    y = damping * M x; y += (1 - sum(y)) / n — the mass-conservation form
    that folds teleport and dangling-node rank into one rank-1 correction,
    so sum(x) stays exactly 1 and the SpMV is the whole iteration.
    Converges when the L1 iterate delta drops below ``tol``."""
    with _Solve("pagerank") as bc:
        if isinstance(A, CSRMatrix):
            ex = get_engine(
                transition_matrix(A), backend=backend, **engine_kw
            )
        elif isinstance(A, SELLMatrix):
            raise TypeError(
                "pagerank needs the CSR adjacency (to build the transition "
                "matrix) or a prebuilt executor over transition_matrix(adj)"
            )
        else:
            ex = _resolve_operator(A, backend=backend, engine_kw=engine_kw)
        n = _require_square(ex, "pagerank")
        mode = _resolve_loop(loop, ex)
        if mode == "host":
            res = _pagerank_host(
                ex, n, damping=damping, tol=tol, maxiter=int(maxiter),
                x0=x0, trace=trace,
            )
        else:
            res = _pagerank_device(
                ex, n, damping=damping, tol=tol, maxiter=int(maxiter),
                x0=x0, trace=trace, mode=mode,
            )
    res.schedule_builds = bc.builds
    return res


def _pagerank_device(ex, n, *, damping, tol, maxiter, x0, trace,
                     mode) -> SolveResult:
    def start():
        dtype = _default_dtype()  # f32, or f64 under jax_enable_x64
        x = (jnp.full((n,), 1.0 / n, dtype) if x0 is None
             else jnp.asarray(x0, dtype))
        damp = jnp.asarray(damping, dtype)
        tolc = jnp.asarray(tol, dtype)
        tr = jnp.zeros((maxiter,), dtype)
        state = (
            x, jnp.asarray(jnp.inf, dtype), jnp.asarray(0, jnp.int32),
            damp, tolc, tr,
        )
        return state, None

    def cond(s):
        _x, delta, k, _damp, tolc, _tr = s
        return (k < maxiter) & (delta > tolc)

    def step(mv, s):
        x, _delta, k, damp, tolc, tr = s
        y = damp * mv(x)
        y = y + (1.0 - jnp.sum(y)) / n
        delta = jnp.sum(jnp.abs(y - x))
        tr = tr.at[k].set(delta)
        return (y, delta, k + 1, damp, tolc, tr)

    def finish(state, _):
        x, delta, k, _, _, tr = state
        iters = int(k)
        delta_f = float(delta)
        return SolveResult(
            x=x,
            iterations=iters,
            residual=delta_f if np.isfinite(delta_f) else float("inf"),
            converged=bool(float(delta) <= tol),
            solver="pagerank",
            loop=mode,
            schedule_builds=0,
            residual_trace=_trace_out(tr, iters, trace),
        )

    return _device_solve(
        "pagerank", ex, mode, maxiter, start, cond, step, finish
    )


def _pagerank_host(ex, n, *, damping, tol, maxiter, x0, trace) -> SolveResult:
    dtype = _default_dtype()  # same source as the device path
    x = (np.full((n,), 1.0 / n, dtype) if x0 is None
         else np.asarray(x0, dtype))
    delta = float("inf")
    tr: List[float] = []
    k = 0
    while k < maxiter and delta > tol:
        y = damping * np.asarray(ex.matvec(jnp.asarray(x)))
        y = y + (1.0 - y.sum()) / n
        delta = float(np.abs(y - x).sum())
        x = y
        tr.append(delta)
        k += 1
    return SolveResult(
        x=x,
        iterations=k,
        residual=delta,
        converged=delta <= tol,
        solver="pagerank",
        loop="host",
        schedule_builds=0,
        residual_trace=np.asarray(tr, dtype) if trace else None,
    )


# --------------------------------------------------------------------------
# Power iteration (dominant eigenpair)


def power_iteration(
    A,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0=None,
    trace: bool = False,
    loop: str = "auto",
    backend: str = "auto",
    **engine_kw,
) -> SolveResult:
    """Dominant eigenpair by power iteration. Convergence metric is the
    relative eigen-residual ||A x - lam x|| / |lam| with lam the Rayleigh
    quotient; `SolveResult.eigenvalue` carries lam. Deterministic default
    start (normalized ones); pass ``x0`` if that is orthogonal to the
    dominant eigenvector."""
    with _Solve("power_iteration") as bc:
        ex = _resolve_operator(A, backend=backend, engine_kw=engine_kw)
        n = _require_square(ex, "power_iteration")
        mode = _resolve_loop(loop, ex)
        if mode == "host":
            res = _power_host(
                ex, n, tol=tol, maxiter=int(maxiter), x0=x0, trace=trace
            )
        else:
            res = _power_device(
                ex, n, tol=tol, maxiter=int(maxiter), x0=x0, trace=trace,
                mode=mode,
            )
    res.schedule_builds = bc.builds
    return res


def _power_device(ex, n, *, tol, maxiter, x0, trace, mode) -> SolveResult:
    def start():
        dtype = _default_dtype()
        x = (jnp.full((n,), 1.0 / math.sqrt(n), dtype) if x0 is None
             else jnp.asarray(x0, dtype))
        x = x / jnp.sqrt(jnp.vdot(x, x))
        tolc = jnp.asarray(tol, dtype)
        tr = jnp.zeros((maxiter,), dtype)
        state = (
            x, jnp.asarray(0.0, dtype), jnp.asarray(jnp.inf, dtype),
            jnp.asarray(0, jnp.int32), tolc, tr,
        )
        return state, None

    def cond(s):
        _x, _lam, delta, k, tolc, _tr = s
        return (k < maxiter) & (delta > tolc)

    def step(mv, s):
        x, _lam, _delta, k, tolc, tr = s
        y = mv(x)
        lam = jnp.vdot(x, y)  # Rayleigh quotient (x is unit-norm)
        resid = y - lam * x
        delta = jnp.sqrt(jnp.vdot(resid, resid)) / jnp.abs(lam)
        x = y / jnp.sqrt(jnp.vdot(y, y))
        tr = tr.at[k].set(delta)
        return (x, lam, delta, k + 1, tolc, tr)

    def finish(state, _):
        x, lam, delta, k, _, tr = state
        iters = int(k)
        return SolveResult(
            x=x,
            iterations=iters,
            residual=float(delta),
            converged=bool(float(delta) <= tol),
            solver="power_iteration",
            loop=mode,
            schedule_builds=0,
            residual_trace=_trace_out(tr, iters, trace),
            eigenvalue=float(lam),
        )

    return _device_solve(
        "power_iteration", ex, mode, maxiter, start, cond, step, finish
    )


def _power_host(ex, n, *, tol, maxiter, x0, trace) -> SolveResult:
    dtype = _default_dtype()
    x = (np.full((n,), 1.0 / math.sqrt(n), dtype) if x0 is None
         else np.asarray(x0, dtype))
    x = x / np.sqrt(np.dot(x, x))
    lam = 0.0
    delta = float("inf")
    tr: List[float] = []
    k = 0
    while k < maxiter and delta > tol:
        y = np.asarray(ex.matvec(jnp.asarray(x)))
        lam = float(np.dot(x, y))
        resid = y - lam * x
        delta = float(np.sqrt(np.dot(resid, resid)) / abs(lam))
        x = y / np.sqrt(np.dot(y, y))
        tr.append(delta)
        k += 1
    return SolveResult(
        x=x,
        iterations=k,
        residual=delta,
        converged=delta <= tol,
        solver="power_iteration",
        loop="host",
        schedule_builds=0,
        residual_trace=np.asarray(tr, dtype) if trace else None,
        eigenvalue=lam,
    )
