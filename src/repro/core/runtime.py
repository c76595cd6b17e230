"""Streaming executor layer: one pipelined execution path for every engine.

The paper's end-to-end win (Sec. II) comes from decoupling the indirect
stream from the processing elements so memory traffic and compute overlap —
the coalescer is a *pipeline stage*, not a patch on the kernel. The serving
analogue of that front-end is the host->device RHS stream: a strictly
synchronous `matmat` serializes "transfer batch, compute batch, transfer
batch, ..." exactly the way an uncoalesced gather serializes index fetch and
element fetch. This module makes the streaming front-end first-class:

  * `Executor` — the protocol every execution engine implements
    (`SpMVEngine`, `ShardedSpMVEngine`). Beyond the synchronous
    `matvec`/`matmat`, an executor exposes the three pipeline hooks the
    streaming layer schedules: ``stage(X)`` (place a RHS micro-batch on the
    executor's device(s) — `jax.device_put`, donated where legal),
    ``dispatch(staged)`` (launch compute asynchronously, no host sync), and
    ``finalize(pending)`` (block and gather). ``matmat`` must equal
    ``finalize(dispatch(stage(X)))`` bit for bit — that identity is what
    makes streamed and synchronous execution interchangeable, and it is
    pinned by tests.
  * `StreamingExecutor` — wraps any `Executor` and micro-batches RHS
    columns through a double-buffered pipeline: while micro-batch *i*
    computes, micro-batch *i+1* is already staging to the device. The
    in-flight window is bounded (``depth``): submitting past it blocks on
    the oldest micro-batch first (backpressure — a serving loop can never
    queue unbounded device memory). `submit()`/`drain()` expose the
    pipeline to serving loops; `matmat()` keeps the drop-in synchronous
    signature.
  * Shared plan/batch geometry — `normalize_to_sell` (the CSR->SELL
    conversion every engine constructor used to duplicate), `pad_width`
    (the width padding the width-aware planner applies), and
    `column_groups`/`microbatch_slices` (balanced vs fixed-size contiguous
    RHS column splits — the sharded engine's model-axis groups and the
    streaming layer's micro-batches are the same operation at two
    granularities).

Dependency direction: this module sits *below* `engine`/`dist` for the
shared geometry helpers (both import it) and *above* them for scheduling
(`StreamingExecutor` talks to engines only through the structural
protocol), so there is no import cycle and `core.runtime` stays importable
on its own.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Protocol, Tuple, Union, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from . import faults
from .formats import CSRMatrix, SELLMatrix, csr_to_sell

DEFAULT_MICROBATCH = 32
DEFAULT_DEPTH = 2


# ---------------------------------------------------------------------------
# Shared plan/batch geometry (extracted from engine.py / dist.py)
# ---------------------------------------------------------------------------


def normalize_to_sell(
    matrix: Union[CSRMatrix, SELLMatrix],
    *,
    slice_height: Optional[int] = None,
    width_multiple: int = 1,
    validate: bool = True,
) -> SELLMatrix:
    """The one CSR->SELL normalization every engine entry point shares.

    CSR inputs are validated and converted (the offline preprocessing step);
    SELL inputs are checked against the requested conversion parameters —
    silently ignoring a `slice_height`/`width_multiple` the matrix does not
    satisfy would hand back a plan with different geometry than the caller
    asked for. ``validate=False`` skips the O(nnz) SELL well-formedness scan
    for hot cache-lookup paths (`get_engine`), where construction on a miss
    validates anyway.
    """
    if isinstance(matrix, CSRMatrix):
        matrix.validate()
        kw = {} if slice_height is None else {"slice_height": slice_height}
        return csr_to_sell(matrix, width_multiple=width_multiple, **kw)
    if isinstance(matrix, SELLMatrix):
        if slice_height is not None and slice_height != matrix.slice_height:
            raise ValueError(
                f"matrix is already SELL with slice_height="
                f"{matrix.slice_height}; cannot re-slice to {slice_height} "
                f"(convert from CSR instead)"
            )
        if width_multiple != 1 and np.any(
            np.asarray(matrix.slice_widths) % width_multiple
        ):
            raise ValueError(
                f"matrix is already SELL and its slice widths are not "
                f"multiples of {width_multiple} (convert from CSR instead)"
            )
        if validate:
            matrix.validate()
        return matrix
    raise TypeError(f"expected CSRMatrix or SELLMatrix, got {type(matrix)}")


def pad_width(
    ci: np.ndarray, va: np.ndarray, *, multiple: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Zero-pad (n_slices, W, H) colidx/value arrays up to the next multiple
    of ``multiple`` columns (colidx 0 / value 0 — safe for SpMV, numerically
    invisible). Returns ``(ci_plan, va_plan, W_plan)``; when W already
    satisfies the multiple the inputs pass through unchanged (identity, so
    no copy on the common path). The width-aware planner shapes plans for
    the execution unit with this before any `BlockSchedule` is built."""
    ns, W, H = ci.shape
    m = int(multiple)
    if m < 1:
        raise ValueError(f"width multiple must be >= 1, got {multiple}")
    W_plan = max(-(-W // m) * m, m)
    if W_plan == W:
        return ci, va, W
    ci_plan = np.zeros((ns, W_plan, H), dtype=ci.dtype)
    va_plan = np.zeros((ns, W_plan, H), dtype=va.dtype)
    ci_plan[:, :W] = ci
    va_plan[:, :W] = va
    return ci_plan, va_plan, W_plan


def column_groups(k: int, n_groups: int) -> List[slice]:
    """Balanced contiguous split of `k` RHS columns into at most `n_groups`
    non-empty slices (fewer when k < n_groups — the k=1 edge keeps one
    group and leaves the rest of the model axis idle)."""
    n_groups = max(1, min(n_groups, k)) if k else 1
    bounds = np.linspace(0, k, n_groups + 1).astype(int)
    return [
        slice(int(bounds[j]), int(bounds[j + 1]))
        for j in range(n_groups)
        if bounds[j + 1] > bounds[j]
    ]


def microbatch_slices(k: int, microbatch: int) -> List[slice]:
    """Fixed-size contiguous split of `k` RHS columns into micro-batches of
    ``microbatch`` columns (the last one may be short). Fixed size — not
    balanced like `column_groups` — because each distinct micro-batch width
    is a separate jit specialization of the executor's batched program: a
    stream of thousands of RHS columns should hit exactly one compiled
    width (plus at most one tail width), not ceil(k/B) different ones."""
    if microbatch < 1:
        raise ValueError(f"microbatch must be >= 1, got {microbatch}")
    return [slice(j, min(j + microbatch, k)) for j in range(0, k, microbatch)]


def data_model_grid(mesh) -> np.ndarray:
    """Normalize a mesh to its (data, model)-ordered 2-D device grid.

    The sharded SpMV path addresses devices as ``grid[data_row, model_col]``
    regardless of the mesh's axis order; any extra axes (e.g. 'pod') must be
    size 1. This is the one place mesh topology is interpreted for SpMV —
    `core.dist.ShardedSpMVEngine` resolves its device grid here, and
    `launch.mesh` re-exports it for CLI-side callers (core must not depend
    on the launch package)."""
    names = mesh.axis_names
    if "data" not in names or "model" not in names:
        raise ValueError(
            f"mesh must carry 'data' and 'model' axes, got {names!r}"
        )
    order = [names.index("data"), names.index("model")]
    extra = [i for i in range(len(names)) if i not in order]
    for i in extra:
        if mesh.devices.shape[i] != 1:
            raise ValueError(
                f"mesh axis {names[i]!r} has size {mesh.devices.shape[i]}; "
                f"only 'data' and 'model' may be > 1 for the sharded SpMV "
                f"engine"
            )
    grid = np.transpose(mesh.devices, order + extra)
    return grid.reshape(grid.shape[0], grid.shape[1])


def parse_stream_spec(spec: str) -> Dict[str, int]:
    """``"depth=D,microbatch=B"`` -> streaming parameters (either key may be
    omitted; defaults fill in). The CLI surface of the streaming layer:
    `serve --spmv --stream depth=2,microbatch=16`."""
    out = {"depth": DEFAULT_DEPTH, "microbatch": DEFAULT_MICROBATCH}
    for part in (p.strip() for p in spec.split(",") if p.strip()):
        key, sep, val = part.partition("=")
        key = key.strip()
        if not sep or key not in out:
            raise ValueError(
                f"--stream expects 'depth=D,microbatch=B' (either key "
                f"optional), got {spec!r}"
            )
        try:
            out[key] = int(val)
        except ValueError:
            raise ValueError(
                f"--stream {key} must be an integer, got {val.strip()!r}"
            )
        if out[key] < 1:
            raise ValueError(f"--stream {key} must be >= 1, got {out[key]}")
    return out


# ---------------------------------------------------------------------------
# The executor protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class Executor(Protocol):
    """What the streaming layer (and any serving loop) requires of an
    execution engine. `SpMVEngine` and `ShardedSpMVEngine` both implement
    it; the contract every implementation must keep is

        finalize(dispatch(stage(X))) == matmat(X)   (bit for bit)

    with `stage` performing only data placement (host->device transfer,
    async), `dispatch` only launching compute (async, no host sync), and
    `finalize` being the single synchronization point.
    """

    @property
    def n_rows(self) -> int: ...

    @property
    def n_cols(self) -> int: ...

    def matvec(self, x): ...

    def matmat(self, X): ...

    def stage(self, X, *, donate: bool = False) -> Any: ...

    def dispatch(self, staged) -> Any: ...

    def finalize(self, pending): ...

    def plan_report(self, **kwargs) -> Dict[str, object]: ...


def device_put_rhs(X, device=None, *, donate: bool = False):
    """`jax.device_put` for a staged RHS micro-batch, donating the source
    buffer when that is legal: only jax arrays can be donated (a numpy
    micro-batch is typically a view of the caller's request buffer — JAX
    ignores donation of host numpy, and the view's backing memory is not
    ours to retire anyway). Micro-batches the streaming layer slices from a
    jax RHS are fresh buffers it owns, so donation is safe and frees the
    staging copy as soon as the transfer lands."""
    donate = bool(donate) and isinstance(X, jax.Array)
    return jax.device_put(X, device, donate=donate)


def proper_slice(sl: slice, k: int) -> bool:
    """The other half of the donation-legality rule: donate a sliced
    micro-batch only when `sl` selects a strict subset of the k source
    columns. JAX short-circuits full-range basic indexing — an identity
    slice returns the *caller's* array object, which every other consumer
    (and the caller) still needs and which the pipeline does not own;
    proper slices mint a fresh buffer per use."""
    return (sl.stop - sl.start) < k


# ---------------------------------------------------------------------------
# The streaming executor
# ---------------------------------------------------------------------------


class StreamHandle:
    """One submitted RHS batch moving through the pipeline. `result()`
    drives the owning `StreamingExecutor` until every micro-batch of this
    batch has been finalized, then assembles the output columns in order."""

    def __init__(self, owner: "StreamingExecutor", k: int, n_parts: int,
                 dtype) -> None:
        self._owner = owner
        self.k = k
        self._parts: List[Optional[np.ndarray]] = [None] * n_parts
        self._remaining = n_parts
        self._dtype = dtype
        self._error: Optional[BaseException] = None
        self._collected = False
        self.retries = 0  # micro-batch retries spent on this batch

    @property
    def error(self) -> Optional[BaseException]:
        """The failure recorded for this batch, if any (after retries)."""
        return self._error

    @property
    def done(self) -> bool:
        return self._remaining == 0 or self._error is not None

    @property
    def failed(self) -> bool:
        return self._error is not None

    def result(self):
        """Block until this batch is complete and return (n_rows, k);
        re-raises the pipeline error if any of its micro-batches failed."""
        return self._owner._complete(self)

    def _deliver(self, idx: int, part) -> None:
        if self._error is not None:
            return  # batch already failed; late part is discarded
        self._parts[idx] = part
        self._remaining -= 1

    def _fail(self, exc: BaseException) -> None:
        """A stage/dispatch/finalize of this batch raised: record it so the
        handle completes (as failed) instead of wedging every waiter."""
        if self._error is None:
            self._error = exc

    def _assemble(self):
        """Column-concatenate the finalized micro-batches. Device results
        stay on device (`jnp.concatenate` — forcing a host copy here would
        tax the streamed path with transfers the synchronous `matmat` never
        pays); host results (the sharded engine gathers to host) use numpy."""
        if self._error is not None:
            raise self._error
        if not self._parts:
            return np.zeros((self._owner.n_rows, 0), self._dtype)
        if len(self._parts) == 1:
            return self._parts[0]
        if all(isinstance(p, np.ndarray) for p in self._parts):
            return np.concatenate(self._parts, axis=1)
        return jnp.concatenate([jnp.asarray(p) for p in self._parts], axis=1)


class StreamTimeout(RuntimeError):
    """A micro-batch's device sync exceeded the pipeline's `timeout`."""


@dataclasses.dataclass
class BatchFailure:
    """One submitted batch that still failed after the pipeline's bounded
    retries — `drain()` reports these instead of raising."""

    index: int  # position in submission order among that drain's batches
    k: int
    error: BaseException
    retries: int


class DrainResult(list):
    """`drain()`'s return value: the healthy batch results in submission
    order (it *is* a list, so existing `drain() == []` / iteration idioms
    hold), plus `failures` — the structured report of batches that failed
    after retries. A healthy drain has ``failures == []``."""

    def __init__(self, results=(), failures: Optional[List[BatchFailure]] = None):
        super().__init__(results)
        self.failures: List[BatchFailure] = list(failures or ())

    @property
    def ok(self) -> bool:
        return not self.failures


class _InflightEntry:
    """One reserved slot in the in-flight window. The slot is reserved
    (appended) under the pipeline lock, but its stage/dispatch runs outside
    the lock — `ready` flips once `pending` holds the dispatched work, and
    retirement only touches ready entries. `X`/`sl` are kept so a failed
    micro-batch can be re-staged from source for a retry."""

    __slots__ = ("handle", "idx", "pending", "ready", "X", "sl", "attempts")

    def __init__(self, handle: StreamHandle, idx: int, X=None, sl=None) -> None:
        self.handle = handle
        self.idx = idx
        self.pending: Any = None
        self.ready = False
        self.X = X
        self.sl = sl
        self.attempts = 0


class StreamingExecutor:
    """Double-buffered micro-batch pipeline over any `Executor`.

    ``matmat(X)`` splits the RHS columns into ``microbatch``-wide
    micro-batches and pipelines them: micro-batch *i+1* is staged to the
    device (`stage` — an async `jax.device_put`, donated where legal) while
    micro-batch *i* computes (`dispatch` — async launch), and results are
    gathered (`finalize`) only when the bounded in-flight window forces it
    or the caller asks. With ``depth >= 2`` the host->device RHS transfer
    therefore overlaps compute on the previous micro-batch — the serving
    analogue of the paper's decoupled index/element streams. ``depth`` is
    the backpressure bound: at most ``depth`` staged-or-computing
    micro-batches exist at once, so device memory for RHS staging is capped
    at ``depth * microbatch`` columns no matter how fast requests arrive.

    ``submit(X)`` feeds the pipeline without waiting for results (it blocks
    only when the in-flight window is full — on the *oldest* micro-batch,
    which is exactly the one whose buffers the new work needs);
    ``drain()`` retires everything in flight and returns the completed
    batches in submission order. ``matmat`` is submit + complete-one, so
    it stays a drop-in for the synchronous signature and is bit-identical
    to the wrapped executor's ``matmat`` (pinned by the parity property
    tests: reference backend exactly, pallas within 1e-5).

    ``depth=1`` degenerates to the synchronous schedule (stage, compute,
    gather, repeat) — useful as the control in A/B throughput runs.

    Thread-safe: a condition variable guards the pipeline state, and the
    blocking device sync (`finalize`) always runs *outside* it — one
    thread waiting on results never prevents another from staging and
    dispatching new micro-batches into free slots.
    """

    def __init__(
        self,
        executor: Executor,
        *,
        microbatch: int = DEFAULT_MICROBATCH,
        depth: int = DEFAULT_DEPTH,
        donate: bool = True,
        timeout: Optional[float] = None,
        retries: int = 0,
        validate: bool = False,
    ) -> None:
        if microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {microbatch}")
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if timeout is not None and not timeout > 0:
            raise ValueError(f"timeout must be > 0 seconds, got {timeout}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        for hook in ("matmat", "stage", "dispatch", "finalize"):
            if not callable(getattr(executor, hook, None)):
                raise TypeError(
                    f"executor {type(executor).__name__} does not implement "
                    f"the Executor protocol (missing {hook}); see "
                    f"core.runtime.Executor"
                )
        self.executor = executor
        self.microbatch = int(microbatch)
        self.depth = int(depth)
        self.donate = bool(donate)
        # Fault tolerance: `timeout` bounds each micro-batch's device sync
        # (`finalize`) in seconds; `retries` bounds how many times a failed
        # or timed-out micro-batch is re-staged from source before its batch
        # is reported failed; `validate` rejects NaN/Inf RHS values at
        # staging time with a clear error instead of streaming poison.
        self.timeout = None if timeout is None else float(timeout)
        self.retries = int(retries)
        self.validate = bool(validate)
        self._stats = {"retries": 0, "timeouts": 0, "failures": 0}
        # Guards _inflight/_submitted/handle state. Notified on every state
        # change (reserve, ready, pop, delivery) so waiters re-check their
        # predicate.
        self._cv = threading.Condition()
        self._inflight: Deque[_InflightEntry] = deque()  # reservation order
        self._submitted: List[StreamHandle] = []

    @property
    def stats(self) -> Dict[str, int]:
        """Pipeline fault counters: micro-batch ``retries``, ``timeouts``
        observed, and batches that still ``failures``-reported after
        retries."""
        with self._cv:
            return dict(self._stats)

    # -- pipeline plumbing --------------------------------------------------

    @property
    def n_rows(self) -> int:
        return self.executor.n_rows

    @property
    def n_cols(self) -> int:
        return self.executor.n_cols

    @property
    def in_flight(self) -> int:
        """Micro-batches currently staged or computing (<= depth always)."""
        with self._cv:
            return len(self._inflight)

    def _retire_oldest(self) -> bool:
        """Finalize the oldest *ready* in-flight micro-batch. The device
        sync runs outside the lock — popping frees an in-flight slot
        immediately, so another thread's submit stages its transfer while
        this one blocks on results. An entry still mid-stage on its
        submitter's thread is skipped (no head-of-line blocking behind a
        slow stage or first-use compile). Returns False when nothing was in
        flight (a concurrent retirer got there first; delivery will be
        notified)."""
        with self._cv:
            while True:
                if not self._inflight:
                    return False
                entry = next((e for e in self._inflight if e.ready), None)
                if entry is not None:
                    break
                # reserved slots exist but none dispatched yet: wait for a
                # submitter to flip one ready (or remove it on failure)
                self._cv.wait()
            self._inflight.remove(entry)
            self._cv.notify_all()  # a window slot is free
        injected_sites: List[str] = []
        while True:
            try:
                faults.maybe_inject(
                    "dispatch_timeout",
                    f"injected micro-batch timeout (part {entry.idx})",
                )
                part = self._finalize_timed(entry.pending)
            except Exception as exc:
                if isinstance(exc, StreamTimeout):
                    with self._cv:
                        self._stats["timeouts"] += 1
                if isinstance(exc, faults.FaultInjected):
                    injected_sites.append(exc.site)
                restaged = False
                while entry.attempts < self.retries and entry.X is not None:
                    # Bounded retry: re-stage this micro-batch from source
                    # (never donated — the source batch outlives retries)
                    # and re-dispatch. A transient device hiccup or an
                    # injected timeout heals here without failing the batch.
                    entry.attempts += 1
                    with self._cv:
                        self._stats["retries"] += 1
                        entry.handle.retries += 1
                    try:
                        staged = self.executor.stage(
                            entry.X[:, entry.sl], donate=False
                        )
                        entry.pending = self.executor.dispatch(staged)
                        restaged = True
                        break
                    except Exception as exc2:
                        exc = exc2  # restage itself failed; spend a retry
                if restaged:
                    continue  # finalize the freshly dispatched work
                # Out of retries. The entry is already popped; without this
                # the handle would never complete and every later
                # result()/drain() would wait forever. Fail the handle —
                # the error surfaces exactly once, at that batch's
                # collector (its result(), or drain().failures) — and
                # count the retirement as progress for whoever drove it,
                # whose own batch may be perfectly healthy.
                with self._cv:
                    self._stats["failures"] += 1
                    entry.handle._fail(exc)
                    self._cv.notify_all()
                return True
            break
        # Retrying past injected faults counts as recovery.
        for site in injected_sites:
            faults.note_recovered(site)
        with self._cv:
            entry.handle._deliver(entry.idx, part)
            self._cv.notify_all()
        return True

    def _finalize_timed(self, pending):
        """`finalize` with the pipeline's per-micro-batch deadline applied.

        The device sync runs on a helper thread only when a timeout is set;
        exceeding it raises `StreamTimeout` (the abandoned sync thread is a
        daemon — it parks on the device handle and dies with the process,
        which is the best a host can do about a truly hung accelerator)."""
        if self.timeout is None:
            return self.executor.finalize(pending)
        box: Dict[str, Any] = {}

        def _run() -> None:
            try:
                box["value"] = self.executor.finalize(pending)
            except BaseException as exc:  # surfaces on the caller thread
                box["error"] = exc

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        t.join(self.timeout)
        if t.is_alive():
            raise StreamTimeout(
                f"micro-batch finalize exceeded timeout={self.timeout}s"
            )
        if "error" in box:
            raise box["error"]
        return box["value"]

    def _pump(self, handle: StreamHandle, X, slices) -> None:
        """Stage + dispatch every micro-batch of `X`, retiring the oldest
        in-flight work whenever the window is full. Because stage/dispatch
        only *launch* async work, micro-batch i+1's transfer is in motion
        while micro-batch i (and, at depth > 2, earlier ones) are still
        computing."""
        try:
            self._pump_inner(handle, X, slices)
        except BaseException as exc:
            # Parts that never got dispatched would otherwise leave the
            # handle incomplete forever (wedging drain()); fail it, count
            # it collected — the submitter receives the error right here —
            # and drop it from _submitted so a long-lived submit()-only
            # serving loop does not accumulate dead handles (and their
            # already-delivered parts) across transient errors.
            with self._cv:
                handle._fail(exc)
                handle._collected = True
                if handle in self._submitted:
                    self._submitted.remove(handle)
                self._cv.notify_all()
            raise

    def _pump_inner(self, handle: StreamHandle, X, slices) -> None:
        for idx, sl in enumerate(slices):
            entry = _InflightEntry(handle, idx, X, sl)
            while True:  # reserve a window slot
                with self._cv:
                    if len(self._inflight) < self.depth:
                        self._inflight.append(entry)
                        self._cv.notify_all()
                        break
                if not self._retire_oldest():
                    with self._cv:
                        if len(self._inflight) >= self.depth:
                            self._cv.wait()
            # Stage + dispatch OUTSIDE the lock: the H2D copy and any
            # first-use jit compile must not stall other threads' submits
            # or retirements — only the slot reservation is serialized.
            try:
                donate = self.donate and proper_slice(sl, handle.k)
                staged = self.executor.stage(X[:, sl], donate=donate)
                pending = self.executor.dispatch(staged)
            except BaseException:
                with self._cv:  # release the reserved slot
                    try:
                        self._inflight.remove(entry)
                    except ValueError:
                        pass
                    self._cv.notify_all()
                raise
            with self._cv:
                entry.pending = pending
                entry.ready = True
                self._cv.notify_all()

    def _complete(self, handle: StreamHandle):
        # Claim the handle *before* waiting: a drain() that sweeps while we
        # block on this batch's last micro-batch must not hand the same
        # result out a second time. result() itself stays a plain read for
        # the handle's owner.
        with self._cv:
            handle._collected = True
        while True:
            with self._cv:
                if handle.done:
                    if handle in self._submitted:
                        self._submitted.remove(handle)
                    return handle._assemble()
            if not self._retire_oldest():
                with self._cv:
                    if not handle.done and not self._inflight:
                        # this handle's remaining parts are mid-finalize on
                        # another thread; wait for their delivery
                        self._cv.wait()

    # -- public API ---------------------------------------------------------

    def submit(self, X) -> StreamHandle:
        """Feed one RHS batch (n_cols, k) into the pipeline. Returns a
        handle whose `result()` blocks for that batch only; blocks here only
        while the bounded in-flight window is full."""
        X = X if isinstance(X, (np.ndarray, jax.Array)) else jnp.asarray(X)
        if X.ndim != 2 or X.shape[0] != self.n_cols:
            raise ValueError(
                f"submit expects X of shape ({self.n_cols}, k), got {X.shape}"
            )
        if self.validate and X.size and not (
            np.all(np.isfinite(X)) if isinstance(X, np.ndarray)
            else bool(jnp.all(jnp.isfinite(X)))
        ):
            raise ValueError(
                "submit rejected RHS batch: non-finite values (NaN/Inf) in X "
                "(validate=True guards the pipeline against poisoned inputs)"
            )
        k = int(X.shape[1])
        slices = microbatch_slices(k, self.microbatch) if k else []
        handle = StreamHandle(self, k, len(slices), X.dtype)
        with self._cv:
            self._submitted.append(handle)
        self._pump(handle, X, slices)
        return handle

    def drain(self) -> DrainResult:
        """Retire all in-flight work; return every not-yet-collected batch's
        result in submission order (an empty `DrainResult` when idle). A
        batch whose `result()` was (or is being) collected by its own thread
        is excluded — drain never re-delivers a claimed batch. (`result()`
        itself stays idempotent for the handle's owner, like a future:
        re-reading your own handle is allowed even after a drain collected
        it.)

        Failures are *reported*, not raised: a batch that still failed after
        the pipeline's bounded retries appears in the returned
        `DrainResult.failures` (index in submission order, k, error, retries
        spent) while every healthy batch's result is delivered normally — a
        single poisoned submission can no longer wedge or mask the rest of
        the pipeline. Callers that want the old throwing behavior check
        ``drain().failures`` themselves."""
        while True:
            if self._retire_oldest():
                continue
            with self._cv:
                if self._inflight:
                    continue  # a concurrent submit refilled the window
                if not all(h.done for h in self._submitted):
                    self._cv.wait()  # parts mid-finalize on another thread
                    continue
                pending = [h for h in self._submitted if not h._collected]
                for h in pending:
                    h._collected = True
                self._submitted = []
            return DrainResult(
                (h._assemble() for h in pending if not h.failed),
                failures=[
                    BatchFailure(
                        index=i, k=h.k, error=h._error, retries=h.retries
                    )
                    for i, h in enumerate(pending) if h.failed
                ],
            )

    def matvec(self, x):
        """Single-RHS convenience: streams a (n_cols, 1) batch."""
        x = jnp.asarray(x)
        if x.ndim != 1 or x.shape[0] != self.n_cols:
            raise ValueError(
                f"matvec expects x of shape ({self.n_cols},), got {x.shape}"
            )
        return self.matmat(x[:, None])[:, 0]

    def matmat(self, X):
        """Y = A @ X through the pipeline — drop-in for `Executor.matmat`,
        bit-identical to it on the reference backend."""
        return self.submit(X).result()

    def __call__(self, x):
        return self.matvec(x) if jnp.asarray(x).ndim == 1 else self.matmat(x)

    # -- introspection ------------------------------------------------------

    def plan_report(self, *, k: Optional[int] = None, **kwargs):
        """The wrapped executor's plan report with the perf model's overlap
        prediction for this pipeline shape filled in under ``streaming``
        (`perfmodel.streaming_spmv_perf` — the transfer/compute overlap
        term). `k` defaults to one full in-flight window."""
        stream = {
            "k": self.depth * self.microbatch if k is None else int(k),
            "microbatch": self.microbatch,
            "depth": self.depth,
        }
        return self.executor.plan_report(stream=stream, **kwargs)
