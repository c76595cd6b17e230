"""Batched SpMV execution engine: plan once, execute many.

The paper's preprocessing split (Sec. III: format conversion and coalescer
metadata are built offline, the data path then streams) maps poorly onto a
library whose entry points rebuild the `BlockSchedule` on every call. This
module makes the plan a first-class, cached object:

  * `cached_block_schedule` — content-addressed schedule cache. The key is the
    SHA-256 digest of the index-stream bytes plus (window, block_rows,
    max_warps); two matrices with byte-identical column-index streams share
    one schedule object, and repeat plans return the *same* object (identity,
    not just equality) so jit caches keyed on it stay warm.
  * `SpMVEngine` — owns one matrix (CSR is converted to SELL up front,
    validated), one schedule, and jit-compiled `matvec(x)` / batched
    `matmat(X)` closures that reuse the schedule across thousands of
    right-hand sides. On the pallas backend `matmat` is the matvec batched
    over RHS columns (`vmap`), one kernel product per column; the reference
    backend runs a direct 2-D schedule gather, bit-identical per column to
    its matvec.
  * Execution backends — ``backend="reference" | "pallas" | "auto"``. The
    reference backend executes the jnp schedule-gather oracle; the pallas
    backend runs the fused `kernels.sell_spmv` kernel (natively on TPU,
    interpret mode elsewhere). The kernel consumes SELL in
    ``cols_per_chunk``-wide chunks, so the *planner* is width-aware: when the
    padded width W is not a multiple of `cols_per_chunk`, the plan geometry
    is padded up (zero columns, colidx 0 / value 0) and the `BlockSchedule`
    is built against the padded stream — the plan is shaped for the execution
    unit at planning time, never patched at run time. ``"auto"`` picks pallas
    on TPU and the reference path elsewhere (interpret mode is a correctness
    tool, not a serving path).
  * Schedule persistence — `cached_block_schedule` backs the in-memory cache
    with digest-named npz files (core.schedule_store) when a cache directory
    is configured (``cache_dir=`` or ``$REPRO_SCHEDULE_CACHE``), so a cold
    process skips `build_block_schedule` entirely for known matrices.
    Engine-planned files embed the matrix content digest and are rejected on
    mismatch.
  * `get_engine` — engine-level cache (keyed on matrix content + plan params)
    so ad-hoc call sites (`spmv_sell_coalesced`, serving loops) hit warm
    compiled paths without threading engine handles around.
  * `SpMVEngine.plan_report()` — surfaces `coalesce_stats` and the cycle-level
    perf-model predictions for the plan, so callers can inspect what the
    adapter would do with this stream before committing to a variant.

Cache sizes are bounded (LRU) — schedules for big matrices hold O(nnz)
metadata and serving processes are long-lived.

Execution entry point: `core/runtime.py`. `SpMVEngine` implements the
`runtime.Executor` protocol (``stage``/``dispatch``/``finalize`` alongside
the synchronous ``matvec``/``matmat``), so serving loops pipeline it through
`runtime.StreamingExecutor` — host->device RHS staging overlapped with
compute on the previous micro-batch — instead of calling `matmat` in
lockstep. The CSR->SELL normalization and plan width padding live in
`runtime` too (shared with `core.dist`).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
from collections import OrderedDict
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import faults, schedule_store
from .coalescer import BlockSchedule, META_BYTES_PACKED, \
    META_BYTES_UNPACKED, build_block_schedule, coalesce_stats, \
    packable_schedule, schedule_gather_reference, schedule_meta_bytes, \
    trim_schedule_warps
from .formats import CSRMatrix, SELLMatrix
from .perfmodel import DEFAULT_HW, HWConfig, spmv_perf, \
    streaming_spmv_perf
from .runtime import device_put_rhs, normalize_to_sell, pad_width
from .spans import span

BACKENDS = ("reference", "pallas", "auto")
BACKEND_ENV = "REPRO_BACKEND"
DEFAULT_WINDOW = 256
DEFAULT_COLS_PER_CHUNK = 8
# Coalescing granularity wherever x is not held in VMEM lane rows (see
# `resolve_block_rows`).
DEFAULT_BLOCK_ROWS = 8
PACKED_CHOICES = (True, False, "auto")
# SELL value-storage dtypes. "native" (== None) streams values at the input
# dtype; "bf16"/"f32" store the value stream narrower and accumulate at the
# promoted dtype (kernels and the reference path both promote — the bf16
# numerics gate lives in tests/test_bf16.py).
VALUE_DTYPES = ("native", "bf16", "f32")


def resolve_value_dtype(value_dtype: Optional[str]) -> Optional[str]:
    """Normalize the value-storage knob: ``None``/"native" -> None (follow
    the input dtype), otherwise one of `VALUE_DTYPES`."""
    if value_dtype is None or value_dtype == "native":
        return None
    if value_dtype not in VALUE_DTYPES:
        raise ValueError(
            f"value_dtype must be one of {(None,) + VALUE_DTYPES}, got "
            f"{value_dtype!r}"
        )
    return value_dtype


def value_bytes_per_elem(
    value_dtype: Optional[str], hw: "HWConfig" = DEFAULT_HW
) -> float:
    """Bytes per SELL value the plan actually streams — the perf model's
    `value_bytes_per_elem` term (native keeps the model's `hw.elem_bytes`)."""
    resolved = resolve_value_dtype(value_dtype)
    if resolved is None:
        return float(hw.elem_bytes)
    return {"bf16": 2.0, "f32": 4.0}[resolved]


def _runtime_one(x: jnp.ndarray) -> jnp.ndarray:
    """An exact scalar 1.0 the compiler must treat as a runtime value:
    ``sum(x[:1]) * 0 + 1`` cannot be constant-folded without fast-math
    (``x[0]`` could be inf/nan), yet equals 1.0 bitwise for any finite
    input. Feeding it to `_width_tree_sum` defeats FMA contraction there."""
    s = jnp.sum(x.reshape(-1)[:1])
    return s * s.dtype.type(0) + s.dtype.type(1)


def _width_tree_sum(prod: jnp.ndarray, one: jnp.ndarray) -> jnp.ndarray:
    """Reduce ``(n_slices, W, ...)`` over the width axis with a fixed
    power-of-two halving tree. Unlike `jnp.sum` — whose reduction tree
    depends on W, so ULP-level results change with padding — this reduction
    is bitwise invariant to trailing zero columns: padding W up to a larger
    power of two only inserts ``x + 0.0`` identity folds on top of the same
    tree. That invariance is what lets `core.dist` pad each row shard to its
    *own* max slice width (collapsing padded nnz on skewed matrices) while
    staying bit-identical to the single-device engine.

    ``one`` must be `_runtime_one(...)` of a kernel input. Multiplying the
    product by it blocks the one rewrite XLA/LLVM would otherwise apply:
    contracting the producing multiply into the first fold as an FMA, whose
    extra-precision lanes vary with the padded width. After this multiply
    the folds only ever see ``p * one`` operands, and the worst contraction
    available is ``fma(p, 1.0, q)`` — which rounds bitwise identically to
    the plain add — so every fold is exact at any width."""
    if prod.shape[1] == 0:
        return jnp.zeros(prod.shape[:1] + prod.shape[2:], prod.dtype)
    prod = prod * one
    p = 1
    while p < prod.shape[1]:
        p *= 2
    if p != prod.shape[1]:
        pad = [(0, 0)] * prod.ndim
        pad[1] = (0, p - prod.shape[1])
        prod = jnp.pad(prod, pad)
    while prod.shape[1] > 1:
        h = prod.shape[1] // 2
        prod = prod[:, :h] + prod[:, h:]
    return prod[:, 0]


def resolve_packed(packed: Union[bool, str], schedule: BlockSchedule) -> bool:
    """The engine-level packing rule, shared with `plan_report`: ``"auto"``
    packs whenever the schedule's geometry fits the 16/16-bit encoding
    (`coalescer.packable_schedule`), an explicit bool is honored as-is
    (``True`` on an unpackable geometry raises at plan-build time in
    `kernels.sell_spmv.build_device_plan`)."""
    if packed == "auto":
        return packable_schedule(schedule)
    return bool(packed)


def resolve_backend(backend: str) -> str:
    """Map "auto" to a concrete executor: the ``REPRO_BACKEND`` env var when
    set (empty string = unset — one variable flips every auto call site in
    a serve/benchmark process instead of threading --backend through each
    CLI), otherwise pallas on TPU
    (native compile) and the jnp reference elsewhere — interpret-mode pallas
    is for correctness checks, not serving. Explicit "reference"/"pallas"
    arguments always win over the environment."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        env = (os.environ.get(BACKEND_ENV) or "").strip()
        if env and env != "auto":
            if env not in BACKENDS:
                raise ValueError(
                    f"${BACKEND_ENV} must be one of {BACKENDS} (or empty = "
                    f"unset), got {env!r}"
                )
            return env
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return backend


def resolve_window(
    window: Optional[int],
    *,
    backend_resolved: str,
    cols_per_chunk: int,
    slice_height: int,
) -> int:
    """The engine's window-resolution rule, shared by `SpMVEngine.__init__`
    and the `get_engine` cache key: the pallas backend structurally plans one
    (slice, chunk) per window (an explicit window that fights that geometry
    raises), the reference backend defaults to `DEFAULT_WINDOW`. Keying the
    engine cache on the *resolved* window means every spelling of the same
    plan — ``window=None``, an explicit 256 (reference), an explicit
    ``cols_per_chunk * slice_height`` (pallas) — lands on one engine instead
    of building duplicate schedules and duplicate jit compiles."""
    if backend_resolved == "pallas":
        kernel_window = int(cols_per_chunk) * int(slice_height)
        if window is not None and int(window) != kernel_window:
            raise ValueError(
                f"backend='pallas' plans one (slice, chunk) per window: "
                f"window = cols_per_chunk * slice_height = {kernel_window}"
                f", but window={window} was requested (pass window=None "
                f"to derive it, or change cols_per_chunk)"
            )
        return kernel_window
    return DEFAULT_WINDOW if window is None else int(window)


def _storage_dtype(value_dtype: Optional[str]):
    """The jnp dtype a resolved value-storage knob stores values in, or
    None to follow the input."""
    return {"bf16": jnp.bfloat16, "f32": jnp.float32, None: None}[value_dtype]


def resolve_block_rows(
    block_rows: Optional[int],
    sell: SELLMatrix,
    *,
    backend_resolved: str,
    window: int,
    value_dtype: Optional[str],
) -> int:
    """The engine's coalescing granularity, shared by `SpMVEngine.__init__`,
    the `get_engine` cache key and `ShardedSpMVEngine`. An explicit
    `block_rows` is honoured. ``None`` resolves to a whole 128-lane row
    (`kernels.sell_spmv.LANES`) where the pallas plan will hold x in VMEM
    (`kernels.sell_spmv.resident_geometry` on the matrix's columns, stored
    value dtype, window and slice height): every warp there loads a whole
    lane row, so coalescing at that width fetches no more and runs fewer
    warps. Elsewhere it resolves to `DEFAULT_BLOCK_ROWS`."""
    if block_rows is not None:
        return int(block_rows)
    if backend_resolved == "pallas":
        # Local: core stays importable before the kernels package.
        from repro.kernels.sell_spmv import LANES, resident_geometry

        dtype = _storage_dtype(value_dtype) or jax.dtypes.canonicalize_dtype(
            sell.values.dtype)
        if resident_geometry(sell.n_cols, dtype, window=window,
                             slice_height=sell.slice_height):
            return LANES
    return DEFAULT_BLOCK_ROWS


# ---------------------------------------------------------------------------
# Content-addressed schedule cache
# ---------------------------------------------------------------------------

_SCHEDULE_CACHE_MAX = 64
_ENGINE_CACHE_MAX = 32  # > the 20-matrix benchmark suite, so one pass fits


class _LRUCache:
    """Tiny bounded LRU with hit/miss counters (OrderedDict-backed).

    Thread-safe: the serving loop this repo is growing toward calls
    `get_engine` from multiple request threads, and an unguarded
    OrderedDict mutates (`move_to_end` + `popitem`) under every get/put."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: "OrderedDict[object, object]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key, *, count: bool = True):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                if count:
                    self.hits += 1
                return self._d[key]
            if count:
                self.misses += 1
            return None

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


_schedule_cache = _LRUCache(_SCHEDULE_CACHE_MAX)
_engine_cache = _LRUCache(_ENGINE_CACHE_MAX)
# Serializes the miss path of `get_engine` (lookup + construct + insert):
# engine construction is cheap (planning/compilation are lazy), and holding
# one lock guarantees concurrent callers with the same key observe a single
# engine object rather than racing two into existence.
_engine_lock = threading.RLock()

# Plan-construction counters, distinct from the LRU's hit/miss pair: `built`
# counts actual `build_block_schedule` invocations (the cost persistence
# exists to avoid), the disk_* counters observe the persistent layer. The CI
# round-trip gate asserts built == 0 for a cold process with a warm disk cache.
_plan_stats = {
    "built": 0,
    "disk_hits": 0,
    "disk_rejects": 0,
    "disk_saves": 0,
    # Self-healing counters: a `rebuild` is a plan rebuilt because its disk
    # file failed validation and was quarantined (`*.bad`); `save_errors`
    # counts writes that still failed after the store's bounded retries
    # (persistence degrades to memory-only rather than failing planning).
    "rebuilds": 0,
    "save_errors": 0,
}
_plan_stats_lock = threading.Lock()

# Per-plan-key build locks: concurrent planners of the *same* stream
# serialize (one builds, the rest get the cached object — preserving the
# identity guarantee under threads), while unrelated plans build in
# parallel. Reentrant: the memory-hit write-through also takes its plan's
# lock, including from inside the locked build path. The table is bounded
# (generously above the schedule LRU) so a long-lived process planning an
# unbounded stream of distinct matrices doesn't leak a lock per plan ever
# seen; evicting a lock another thread still holds only means two builders
# of that plan may race once, which is benign (last put wins).
_BUILD_LOCKS_MAX = 4 * _SCHEDULE_CACHE_MAX
_build_locks: "OrderedDict[object, threading.RLock]" = OrderedDict()
_build_locks_guard = threading.Lock()


def _bump(counter: str, by: int = 1) -> None:
    with _plan_stats_lock:
        _plan_stats[counter] += by


def _build_lock_for(key) -> threading.RLock:
    with _build_locks_guard:
        lock = _build_locks.get(key)
        if lock is None:
            lock = _build_locks[key] = threading.RLock()
        _build_locks.move_to_end(key)
        while len(_build_locks) > _BUILD_LOCKS_MAX:
            _build_locks.popitem(last=False)
        return lock


def stream_digest(indices: np.ndarray) -> str:
    """SHA-256 of an index stream's bytes (plus shape/dtype, so e.g. an int32
    and an int64 view of the same bytes don't collide)."""
    with span("planner.digest"):
        arr = np.ascontiguousarray(np.asarray(indices))
        h = hashlib.sha256()
        h.update(str((arr.shape, arr.dtype.str)).encode())
        h.update(arr.tobytes())
        return h.hexdigest()


def cached_block_schedule(
    indices: np.ndarray,
    *,
    window: int,
    block_rows: int,
    max_warps: Optional[int] = None,
    cache_dir: Optional[str] = None,
    matrix_digest: Optional[str] = None,
) -> Tuple[BlockSchedule, bool]:
    """Build (or fetch) the coalescer schedule for an index stream.

    Returns ``(schedule, was_cached)``. Repeat calls with a byte-identical
    stream and the same plan parameters return the identical schedule object.

    Built schedules are warp-trimmed (`trim_schedule_warps`): the tag matrix
    keeps only the warp columns the stream actually uses, which shrinks both
    the kernel grid and the persisted metadata.

    When a cache directory is configured (``cache_dir=`` or the
    ``$REPRO_SCHEDULE_CACHE`` env var), an in-memory miss falls through to
    the persistent store before planning, and fresh plans are written back —
    digest-named npz files validated on load (stream digest always;
    `matrix_digest` too when both sides carry one). Disk hits count as
    ``was_cached=True``: the plan was not rebuilt. An in-memory *hit* still
    writes through to the store when the file is missing (a plan built before
    the directory was configured must not be lost to the next process).
    """
    digest = stream_digest(indices)
    key = (digest, window, block_rows, max_warps)
    sched = _schedule_cache.get(key)
    if sched is not None:
        _write_through_if_missing(
            sched, digest, window=window, block_rows=block_rows,
            max_warps=max_warps, cache_dir=cache_dir,
            matrix_digest=matrix_digest,
        )
        return sched, True

    with _build_lock_for(key):
        # A concurrent planner of the same stream may have finished while we
        # waited; the re-check keeps the identity guarantee under threads.
        sched = _schedule_cache.get(key, count=False)
        if sched is not None:
            _write_through_if_missing(
                sched, digest, window=window, block_rows=block_rows,
                max_warps=max_warps, cache_dir=cache_dir,
                matrix_digest=matrix_digest,
            )
            return sched, True

        cache_dir = schedule_store.resolve_cache_dir(cache_dir)
        path = None
        rebuilding = False
        if cache_dir:
            path = schedule_store.schedule_path(
                cache_dir, digest, window=window, block_rows=block_rows,
                max_warps=max_warps, matrix_digest=matrix_digest,
            )
            if os.path.exists(path):
                try:
                    sched = schedule_store.load_schedule(
                        path,
                        expect_stream_digest=digest,
                        expect_window=window,
                        expect_block_rows=block_rows,
                        expect_matrix_digest=matrix_digest,
                    )
                    _bump("disk_hits")
                    _schedule_cache.put(key, sched)
                    return sched, True
                except schedule_store.ScheduleCacheMismatch:
                    # Self-healing: move the broken file out of the way so
                    # the rebuild below can persist a fresh one, and so the
                    # next cold process doesn't trip over the same bytes.
                    _bump("disk_rejects")
                    schedule_store.quarantine(path)
                    rebuilding = True

        with span("planner.schedule"):
            sched = build_block_schedule(
                jnp.asarray(np.asarray(indices, dtype=np.int32)),
                window=window,
                block_rows=block_rows,
                max_warps=max_warps,
            )
            # Materialize now: the cache must hand out ready metadata, not
            # lazy traces.
            sched = jax.tree_util.tree_map(
                lambda a: a.block_until_ready()
                if hasattr(a, "block_until_ready") else a,
                sched,
            )
            sched = trim_schedule_warps(sched)
        _bump("built")
        if rebuilding:
            _bump("rebuilds")
            faults.note_recovered("store_read")
        _schedule_cache.put(key, sched)
        if path is not None:
            _save_best_effort(
                path, sched, stream_digest=digest, matrix_digest=matrix_digest
            )
        return sched, False


def _save_best_effort(path, sched, *, stream_digest, matrix_digest) -> None:
    """Persist a plan, degrading to memory-only if the disk stays broken.

    `save_schedule` already retries transient errors with backoff; if the
    write *still* fails, losing persistence must not fail the computation —
    the freshly built plan is live in the memory cache."""
    try:
        schedule_store.save_schedule(
            path, sched, stream_digest=stream_digest, matrix_digest=matrix_digest
        )
        _bump("disk_saves")
    except OSError:
        _bump("save_errors")


def _write_through_if_missing(
    sched: BlockSchedule,
    digest: str,
    *,
    window: int,
    block_rows: int,
    max_warps: Optional[int],
    cache_dir: Optional[str],
    matrix_digest: Optional[str],
) -> None:
    """Persist an in-memory-cached plan whose file does not exist yet.

    Without this, a plan built before `cache_dir`/`$REPRO_SCHEDULE_CACHE` was
    configured would return on the memory-hit fast path forever and never
    reach disk for direct `cached_block_schedule` callers
    (`SpMVEngine.persist_schedule` only covers the engine path)."""
    cache_dir = schedule_store.resolve_cache_dir(cache_dir)
    if cache_dir is None:
        return
    path = schedule_store.schedule_path(
        cache_dir, digest, window=window, block_rows=block_rows,
        max_warps=max_warps, matrix_digest=matrix_digest,
    )
    # The plan's build lock makes the exists-check + save atomic: two
    # concurrent hitters must produce exactly one file and one disk_saves
    # bump (the write itself is atomic either way; the counter isn't).
    with _build_lock_for((digest, window, block_rows, max_warps)):
        if not os.path.exists(path):
            _save_best_effort(
                path, sched, stream_digest=digest, matrix_digest=matrix_digest
            )


def schedule_cache_stats() -> Dict[str, int]:
    """Plan-cache counters plus the persistence layer's IO-health counters
    (``quarantined`` / ``retries`` from `schedule_store.store_io_stats`)."""
    with _plan_stats_lock:
        snapshot = dict(_plan_stats)
    return {
        "size": len(_schedule_cache),
        "hits": _schedule_cache.hits,
        "misses": _schedule_cache.misses,
        **snapshot,
        **schedule_store.store_io_stats(),
    }


def clear_schedule_cache() -> None:
    """Empty the in-memory schedule cache and zero all counters (including
    the plan/disk and IO-health counters — on-disk files are untouched)."""
    _schedule_cache.clear()
    with _plan_stats_lock:
        for k in _plan_stats:
            _plan_stats[k] = 0
    schedule_store.clear_store_io_stats()


def clear_engine_cache() -> None:
    _engine_cache.clear()


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _sell_content_digest(sell: SELLMatrix) -> str:
    """Content digest of a SELL matrix, memoized on the instance — hashing
    O(nnz) bytes per `get_engine` lookup would put the cost the engine exists
    to amortize right back on the hot path. Mutating a SELLMatrix's arrays
    in place after the first digest is not supported (treat them as frozen,
    like every consumer of the format does)."""
    cached = getattr(sell, "_content_digest", None)
    if cached is not None:
        return cached
    with span("planner.digest"):
        h = hashlib.sha256()
        h.update(
            str((sell.n_rows, sell.n_cols, sell.slice_height)).encode()
        )
        for arr in (sell.slice_ptrs, sell.slice_widths, sell.colidx,
                    sell.values):
            a = np.ascontiguousarray(np.asarray(arr))
            h.update(str((a.shape, a.dtype.str)).encode())
            h.update(a.tobytes())
        digest = h.hexdigest()
    sell._content_digest = digest
    return digest


class SpMVEngine:
    """Plan-once / execute-many SpMV over the coalesced data path.

    ``matrix`` may be CSR (converted to SELL here — the offline preprocessing
    step) or an already-built SELL. The constructor validates the format,
    pads the SELL slices once, and plans the coalescer schedule through the
    content-addressed cache. `matvec`/`matmat` then only execute.

    ``backend`` selects the executor: ``"reference"`` (jnp schedule-gather
    oracle), ``"pallas"`` (fused `kernels.sell_spmv` kernel; native on TPU,
    interpret mode elsewhere), or ``"auto"`` (pallas iff running on TPU).
    The pallas kernel consumes ``cols_per_chunk`` SELL columns per grid step,
    which fixes its plan geometry: the padded width must be a multiple of
    `cols_per_chunk` and the window is ``cols_per_chunk * slice_height`` (one
    (slice, chunk) of the index stream). The planner handles both: plan-level
    width padding (zero columns) plus the derived window, applied *before*
    the `BlockSchedule` is built, so the content-addressed cache keys on the
    exact stream and geometry the kernel executes.

    `core.tune.autotune` searches (`cols_per_chunk`, `block_rows`,
    `packed`, `value_dtype`) for a matrix and feeds the winners back
    through `get_engine`.

    ``plan_width_multiple`` overrides the plan-level width padding (default:
    `cols_per_chunk` for the pallas backend, 1 for the reference backend).
    The reference executor reduces over the real width only, so a padded plan
    is bit-identical to an unpadded one — the property the replanning tests
    pin down.

    ``window=None`` (default) resolves to 256 for the reference backend and
    to the kernel-derived window for pallas; an explicit window that fights
    the pallas geometry raises rather than being silently ignored.

    ``block_rows=None`` (default) resolves to 128, a whole VMEM lane row,
    where the pallas matvec will hold x in VMEM, and to 8 everywhere else
    (`resolve_block_rows`); an explicit value is always honoured.

    ``cache_dir`` (default: ``$REPRO_SCHEDULE_CACHE``) enables persistent
    schedule caching — see `cached_block_schedule`.
    """

    def __init__(
        self,
        matrix: Union[CSRMatrix, SELLMatrix],
        *,
        window: Optional[int] = None,
        block_rows: Optional[int] = None,
        slice_height: Optional[int] = None,
        width_multiple: int = 1,
        backend: str = "auto",
        cols_per_chunk: int = DEFAULT_COLS_PER_CHUNK,
        packed: Union[bool, str] = "auto",
        value_dtype: Optional[str] = None,
        plan_width_multiple: Optional[int] = None,
        cache_dir: Optional[str] = None,
    ):
        with span("planner.convert"):
            sell = normalize_to_sell(
                matrix, slice_height=slice_height,
                width_multiple=width_multiple,
            )
        self.sell = sell
        self.backend = backend  # as requested ("auto" preserved for report)
        self.backend_resolved = resolve_backend(backend)
        # "native"/None follows the input dtype; "bf16"/"f32" store the value
        # stream narrower (accumulation promotes — both executors multiply
        # into the RHS dtype). The tuner searches this via DEFAULT_SPACE.
        self.value_dtype = resolve_value_dtype(value_dtype)
        self.cols_per_chunk = int(cols_per_chunk)
        if self.cols_per_chunk < 1:
            raise ValueError(f"cols_per_chunk must be >= 1, got {cols_per_chunk}")
        if packed not in PACKED_CHOICES:
            raise ValueError(
                f"packed must be one of {PACKED_CHOICES}, got {packed!r}"
            )
        self.packed = packed  # as requested; resolved against the schedule
        self.cache_dir = schedule_store.resolve_cache_dir(cache_dir)

        self.window = resolve_window(
            window,
            backend_resolved=self.backend_resolved,
            cols_per_chunk=self.cols_per_chunk,
            slice_height=sell.slice_height,
        )
        self.block_rows = resolve_block_rows(
            block_rows, sell, backend_resolved=self.backend_resolved,
            window=self.window, value_dtype=self.value_dtype,
        )
        if plan_width_multiple is None:
            plan_width_multiple = (
                self.cols_per_chunk if self.backend_resolved == "pallas" else 1
            )
        self.plan_width_multiple = int(plan_width_multiple)

        # Planning is lazy: perf-model queries (`perf`) never pay for padding,
        # schedule construction, or compilation — only execution does.
        # Reentrant because the ensure-chain nests (compile -> schedule ->
        # plan -> padded), and a lock so concurrent matvec/matmat callers
        # plan and compile exactly once.
        self._plan_lock = threading.RLock()
        self._padded = None  # (values (n_slices, W, H), stream, W)
        self._ci3 = None  # colidx (n_slices, W, H) — kept for plan padding
        self._plan = None  # (ci_plan, va_plan, stream, W_real, W_plan)
        self._schedule: Optional[BlockSchedule] = None
        self.plan_cached: Optional[bool] = None  # set when the plan is built
        self._device_plan = None  # kernels.sell_spmv.DevicePlan (pallas only)
        # Device-resident plan arrays, passed to the jitted executables as
        # arguments (never closed over, which would embed O(nnz) constants
        # in every compiled program), one copy per device that runs them.
        self._operands = {}
        self._matvec = None
        self._matmat = None

    # -- planning ----------------------------------------------------------

    def _ensure_padded(self):
        with self._plan_lock:
            if self._padded is None:
                from .spmv import _sell_padded  # local: spmv routes via engine

                ci, va, W = _sell_padded(self.sell)
                self._ci3 = ci
                self._padded = (va, np.ascontiguousarray(ci.reshape(-1)), W)
            return self._padded

    def _ensure_plan(self):
        """Width-aware plan geometry: pad the SELL width up to
        `plan_width_multiple` (zero columns: colidx 0 / value 0, safe for
        SpMV) and lay out the index stream the executor will actually
        consume. Returns ``(ci_plan, va_plan, stream, W_real, W_plan)`` with
        the arrays shaped (n_slices, W_plan, H)."""
        with self._plan_lock:
            return self._ensure_plan_locked()

    def _ensure_plan_locked(self):
        if self._plan is None:
            with span("planner.convert"):
                va, stream, W = self._ensure_padded()
                ci_plan, va_plan, W_plan = pad_width(
                    self._ci3, va, multiple=self.plan_width_multiple
                )
                if W_plan != W:
                    stream = np.ascontiguousarray(ci_plan.reshape(-1))
            self._plan = (ci_plan, va_plan, stream, W, W_plan)
            # The base padded arrays are now redundant (the plan holds what
            # execution needs); drop them so a padded pallas engine doesn't
            # retain two O(nnz_padded) copies for its lifetime. Direct
            # `_ensure_padded` callers just recompute lazily.
            self._padded = None
            self._ci3 = None
        return self._plan

    @property
    def schedule(self) -> BlockSchedule:
        """The coalescer plan (content-addressed cache; built on first use,
        loaded from the persistent store when one is configured)."""
        with self._plan_lock:
            if self._schedule is None:
                _, _, stream, _, _ = self._ensure_plan()
                self._schedule, self.plan_cached = cached_block_schedule(
                    stream,
                    window=self.window,
                    block_rows=self.block_rows,
                    cache_dir=self.cache_dir,
                    matrix_digest=_sell_content_digest(self.sell),
                )
            return self._schedule

    def persist_schedule(self, cache_dir: Optional[str] = None) -> Optional[str]:
        """Write the already-built schedule to the persistent store (no-op if
        no schedule has been planned yet, no directory is configured, or the
        file already exists). Returns the file path, or None. Plans built
        *after* a cache directory is set persist automatically; this covers
        the adopt-a-directory-later path (`get_engine(..., cache_dir=...)`
        hitting an engine that already planned without one)."""
        with self._plan_lock:
            cache_dir = schedule_store.resolve_cache_dir(
                cache_dir if cache_dir is not None else self.cache_dir
            )
            if cache_dir is None or self._schedule is None:
                return None
            _, _, stream, _, _ = self._ensure_plan()
            digest = stream_digest(stream)
            matrix_digest = _sell_content_digest(self.sell)
            path = schedule_store.schedule_path(
                cache_dir, digest, window=self.window,
                block_rows=self.block_rows, matrix_digest=matrix_digest,
            )
            if not os.path.exists(path):
                _save_best_effort(
                    path, self._schedule, stream_digest=digest,
                    matrix_digest=matrix_digest,
                )
            return path

    def _ensure_compiled(self):
        with self._plan_lock:
            if self._matvec is None:
                with span("planner"):
                    return self._ensure_compiled_locked()
            return self._matvec, self._matmat

    def _ensure_compiled_locked(self):
        if self._matvec is None:
            ci_plan, va_plan, stream, W, W_plan = self._ensure_plan()
            sched = self.schedule
            sell = self.sell
            n_slices, H = sell.n_slices, sell.slice_height
            n_rows, n_out = sell.n_rows, stream.shape[0]
            # Named after what they run: a profile's module line reads
            # jit_engine_matvec, jit_engine_matmat and so on.
            # Narrow value storage: cast the hoisted value plan once per
            # trace; the multiply promotes back to the RHS dtype (f32
            # accumulation for bf16 values).
            vdt = _storage_dtype(self.value_dtype)

            if self.backend_resolved == "pallas":
                cpc = self.cols_per_chunk
                block_rows = self.block_rows
                with span("planner.lower") as counts:
                    # Locals to the kernels package are lazy: core must stay
                    # importable before kernels (which itself imports core).
                    # The first engine of a process pays the import here.
                    from repro.kernels.ops import resolve_interpret
                    from repro.kernels.sell_spmv import build_device_plan, \
                        device_operands, grid_steps, lane_gathers, \
                        sell_spmv_pallas

                    # Lower the schedule to the kernel-ready device plan
                    # exactly once; matvec and matmat share it. The schedule already encodes every gather,
                    # so the column-index array is never shipped into a
                    # kernel call (colidx=None). `packed` resolves here
                    # against the real schedule geometry (auto: one int32
                    # word per element whenever lossless).
                    plan = build_device_plan(
                        sched, n_slices=n_slices, cols_per_chunk=cpc,
                        slice_height=H, packed=self.packed,
                    )
                    # The stream in the layout its matvec path reads
                    # (lane-dense where x stays in VMEM, else chunk rows),
                    # so the reshape below and the kernel's own cancel and
                    # no call relayouts.
                    operands = jax.block_until_ready(device_operands(
                        plan, jnp.asarray(va_plan, vdt), sell.n_cols
                    ))
                    plan = self._device_plan = operands[1]
                    counts["grid_steps"] = grid_steps(plan)
                    counts["x_resident"] = int(plan.lane_dense)
                    counts["block_rows"] = plan.block_rows
                    counts["max_warps"] = plan.max_warps
                    counts["lane_gathers"] = lane_gathers(plan)
                interpret = resolve_interpret()

                def _values(va, dtype):
                    return va.reshape(n_slices, W_plan, H).astype(
                        vdt if vdt is not None else dtype
                    )

                def engine_matvec(ops, x: jnp.ndarray) -> jnp.ndarray:
                    va, plan = ops
                    y = sell_spmv_pallas(
                        None,
                        _values(va, x.dtype),
                        x,
                        cols_per_chunk=cpc,
                        block_rows=block_rows,
                        plan=plan,
                        interpret=interpret,
                    )
                    return y[:n_rows]

                def engine_matmat(ops, X: jnp.ndarray) -> jnp.ndarray:
                    if X.shape[1] == 0:  # no columns, no kernel launch
                        return jnp.zeros((n_rows, 0), jnp.promote_types(
                            vdt if vdt is not None else X.dtype, X.dtype))
                    # One kernel product per column: on a lane-dense plan
                    # the resident kernel's `sequential_vmap` loops over
                    # them.
                    return jax.vmap(engine_matvec, in_axes=(None, 1),
                                    out_axes=1)(ops, X)

                matmat = engine_matmat

            else:
                with span("planner.lower"):
                    operands = jax.block_until_ready(
                        (sched, jnp.asarray(va_plan[:, :W], vdt))
                    )

                def engine_matvec(ops, x: jnp.ndarray) -> jnp.ndarray:
                    sched, va = ops
                    gathered = schedule_gather_reference(
                        x[:, None], sched, n_out=n_out
                    )
                    g = gathered[:, 0].reshape(n_slices, W_plan, H)[:, :W]
                    va = va.astype(vdt if vdt is not None else x.dtype)
                    # Width reduction through the padding-invariant tree:
                    # shards padded to their own (smaller) max width stay
                    # bit-identical to the global-width single-device plan.
                    y = _width_tree_sum(va * g, _runtime_one(x))
                    return y.reshape(-1)[:n_rows]

                def engine_matmat_ref(ops, X: jnp.ndarray) -> jnp.ndarray:
                    # Direct 2-D variant of matvec: same gather, same
                    # product, same tree folds per column (the folds are
                    # exact, so per-column bit-identity to matvec is
                    # structural), with one shared gather pass per batch.
                    sched, va = ops
                    k = X.shape[1]
                    if k == 0:  # reshape(-1, 0) below can't infer a size
                        return jnp.zeros((n_rows, 0), X.dtype)
                    gathered = schedule_gather_reference(
                        X, sched, n_out=n_out
                    )
                    g = gathered.reshape(n_slices, W_plan, H, k)[:, :W]
                    va = va.astype(vdt if vdt is not None else X.dtype)
                    y = _width_tree_sum(va[..., None] * g, _runtime_one(X))
                    return y.reshape(-1, k)[:n_rows]

                matmat = engine_matmat_ref

            self._operands = {None: operands}
            self._matvec = jax.jit(engine_matvec)
            self._matmat = jax.jit(matmat)
        return self._matvec, self._matmat

    # -- execution ---------------------------------------------------------

    @property
    def n_rows(self) -> int:
        return self.sell.n_rows

    @property
    def n_cols(self) -> int:
        return self.sell.n_cols

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = A @ x through the cached coalesced plan. x: (n_cols,)."""
        x = jnp.asarray(x)
        if x.ndim != 1 or x.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"matvec expects x of shape ({self.sell.n_cols},), got {x.shape}"
            )
        mv, _ = self._ensure_compiled()
        with span("engine.matvec"):
            return mv(self._operands_for(x), x)

    def _operands_for(self, x: jnp.ndarray):
        """The plan arrays on the device `x` lives on (placed there once), so
        each executable runs where its input was staged."""
        concrete = isinstance(x, jax.Array) and not isinstance(
            x, jax.core.Tracer
        )
        devices = x.devices() if concrete else ()
        dev = next(iter(devices)) if len(devices) == 1 else None
        with self._plan_lock:
            ops = self._operands.get(dev)
            if ops is None:
                ops = self._operands[dev] = jax.device_put(
                    self._operands[None], dev
                )
            return ops

    def device_matvec(self):
        """``(apply, operands)`` with ``apply(operands, x) == matvec(x)``:
        the jitted matvec and the device-resident plan arrays it takes as
        arguments — traceable inside `jax.lax.while_loop` bodies. A solver
        loop passes `operands` into its own jitted driver, so the plan
        enters the loop as loop-invariant state with zero host round-trips
        per iteration and no O(nnz) constant in the compiled loop
        (core.solvers builds on this)."""
        mv, _ = self._ensure_compiled()
        return mv, self._operands[None]

    def matmat(self, X: jnp.ndarray) -> jnp.ndarray:
        """Y = A @ X for X: (n_cols, k) — one schedule shared by all k.

        Column j is bit-identical to ``matvec(X[:, j])`` on either backend:
        pallas runs the matvec batched over the columns, the reference
        backend one 2-D schedule gather with the matvec's folds."""
        X = jnp.asarray(X)
        if X.ndim != 2 or X.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"matmat expects X of shape ({self.sell.n_cols}, k), got {X.shape}"
            )
        _, mm = self._ensure_compiled()
        with span("engine.matmat"):
            return mm(self._operands_for(X), X)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.matvec(x) if jnp.asarray(x).ndim == 1 else self.matmat(x)

    # -- streaming pipeline hooks (core.runtime.Executor protocol) ---------
    # matmat(X) == finalize(dispatch(stage(X))) bit for bit; stage moves
    # data, dispatch launches compute, finalize is the only host sync.

    def stage(self, X: jnp.ndarray, *, donate: bool = False) -> jnp.ndarray:
        """Place a RHS micro-batch on the default device (async transfer;
        the compiled executables run wherever their input lives, with the
        plan arrays placed there once). Donation retires jax-array sources — see
        `runtime.device_put_rhs` for when that is legal."""
        if X.ndim != 2 or X.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"stage expects X of shape ({self.sell.n_cols}, k), got "
                f"{X.shape}"
            )
        return device_put_rhs(X, donate=donate)

    def dispatch(self, staged: jnp.ndarray) -> jnp.ndarray:
        """Launch the batched matmat on an already-staged micro-batch —
        async (JAX dispatch), no host synchronization."""
        return self.matmat(staged)

    def finalize(self, pending: jnp.ndarray) -> jnp.ndarray:
        """Block until a dispatched micro-batch's result is materialized."""
        return jax.block_until_ready(pending)

    # -- introspection -----------------------------------------------------

    def perf(self, system: str, hw: HWConfig = DEFAULT_HW):
        """Cycle-level perf-model prediction for this matrix on one system
        ('base' | 'pack0' | 'pack64' | 'pack256')."""
        return spmv_perf(self.sell, system, hw)

    def plan_report(
        self,
        hw: HWConfig = DEFAULT_HW,
        *,
        stream: Optional[Dict[str, int]] = None,
    ) -> Dict[str, object]:
        """The plan, inspectable: stream/coalescer stats + model predictions.
        Forces planning (this reports on the actual plan, not an estimate).
        ``stream={"k": ..., "microbatch": ..., "depth": ...}`` adds the perf
        model's streamed-throughput prediction (transfer/compute overlap —
        `perfmodel.streaming_spmv_perf`) under ``streaming``; wrapping the
        engine in `runtime.StreamingExecutor` and calling its `plan_report`
        fills these in from the live pipeline shape."""
        sched = self.schedule
        _, _, plan_stream, W, W_plan = self._ensure_plan()
        wide, rate = coalesce_stats(
            plan_stream, window=self.window, block_rows=self.block_rows
        )
        report: Dict[str, object] = {
            "n_rows": self.sell.n_rows,
            "n_cols": self.sell.n_cols,
            "nnz_padded": self.sell.nnz_padded,
            "slice_height": self.sell.slice_height,
            "padded_width": W,
            "plan_width": W_plan,
            "backend": self.backend,
            "backend_resolved": self.backend_resolved,
            "cols_per_chunk": self.cols_per_chunk,
            "window": self.window,
            "block_rows": self.block_rows,
            "n_windows": sched.n_windows,
            "max_warps": sched.max_warps,
            "schedule_cached": self.plan_cached,
            "wide_accesses": wide,
            "coalesce_rate": rate,
            # Persistence-health snapshot: quarantined/.bad files, retried
            # transient IO, and plans rebuilt after quarantine (process-wide
            # counters — the chaos harness and ops dashboards read these).
            "cache_health": {
                key: schedule_cache_stats()[key]
                for key in ("quarantined", "retries", "rebuilds", "save_errors")
            },
            "perf": {
                system: dataclasses.asdict(self.perf(system, hw))
                for system in ("base", "pack0", "pack256")
            },
        }
        if self.backend_resolved == "pallas":
            # Metadata-encoding report: which encoding this plan ships, its
            # bytes/element, and the model-side mem_util/traffic-ratio shift
            # the narrower stream buys (perfmodel's packed-traffic term).
            packed_resolved = resolve_packed(self.packed, sched)
            bytes_packed = schedule_meta_bytes(sched, packed=True)
            bytes_unpacked = schedule_meta_bytes(sched, packed=False)
            perf_by_enc = {
                enc: spmv_perf(
                    self.sell, "pack256", hw,
                    meta_bytes_per_elem=bpe,
                )
                for enc, bpe in (
                    ("packed", META_BYTES_PACKED),
                    ("unpacked", META_BYTES_UNPACKED),
                )
            }
            report["metadata"] = {
                "requested": self.packed,
                "packed": packed_resolved,
                "packable": packable_schedule(sched),
                "meta_bytes_per_element": (
                    META_BYTES_PACKED if packed_resolved
                    else META_BYTES_UNPACKED
                ),
                "meta_bytes": schedule_meta_bytes(
                    sched, packed=packed_resolved
                ),
                "meta_bytes_packed": bytes_packed,
                "meta_bytes_unpacked": bytes_unpacked,
                # Tags ship either way, so the stream-level reduction is
                # slightly under the 2x element-word reduction.
                "traffic_reduction": bytes_unpacked / bytes_packed,
                "mem_util_packed": perf_by_enc["packed"].mem_utilization,
                "mem_util_unpacked": perf_by_enc["unpacked"].mem_utilization,
                "traffic_ratio_packed": perf_by_enc["packed"].traffic_ratio,
                "traffic_ratio_unpacked":
                    perf_by_enc["unpacked"].traffic_ratio,
            }
        # Value-storage report (both backends): the model-side traffic shift
        # a narrower value stream buys, mirroring the metadata section. The
        # numerics side is pinned separately (tests/test_bf16.py).
        vbpe = value_bytes_per_elem(self.value_dtype, hw)
        perf_native_v = spmv_perf(self.sell, "pack256", hw)
        perf_active_v = (
            spmv_perf(self.sell, "pack256", hw, value_bytes_per_elem=vbpe)
            if self.value_dtype is not None else perf_native_v
        )
        report["values"] = {
            "value_dtype": self.value_dtype or "native",
            "value_bytes_per_element": vbpe,
            "mem_util": perf_active_v.mem_utilization,
            "traffic_ratio": perf_active_v.traffic_ratio,
            "traffic_ratio_native": perf_native_v.traffic_ratio,
            "traffic_reduction": (
                perf_native_v.offchip_bytes / perf_active_v.offchip_bytes
            ),
        }
        if stream is not None:
            report["streaming"] = {
                **{key: int(v) for key, v in stream.items()},
                "perf": {
                    system: dataclasses.asdict(
                        streaming_spmv_perf(self.sell, system, hw=hw, **stream)
                    )
                    for system in ("base", "pack256")
                },
            }
        return report


@span("planner")
def get_engine(
    matrix: Union[CSRMatrix, SELLMatrix],
    *,
    window: Optional[int] = None,
    block_rows: Optional[int] = None,
    slice_height: Optional[int] = None,
    width_multiple: int = 1,
    backend: str = "auto",
    cols_per_chunk: int = DEFAULT_COLS_PER_CHUNK,
    packed: Union[bool, str] = "auto",
    value_dtype: Optional[str] = None,
    cache_dir: Optional[str] = None,
) -> SpMVEngine:
    """Engine cache: same matrix content + plan params -> same engine (and
    therefore same compiled matvec/matmat). CSR inputs are keyed on the SELL
    they convert to, so CSR and its converted SELL share an engine. The key
    includes the *resolved* backend, the *resolved* window, the *resolved*
    `block_rows` — exactly the resolution
    `SpMVEngine.__init__` performs, so ``window=None`` and its explicit
    spelling (256 for reference, `cols_per_chunk * slice_height` for
    pallas), and ``block_rows=None`` and the value it resolves to
    (`resolve_block_rows`), share one engine instead of duplicating
    schedules and jit compiles — and, for pallas,
    `cols_per_chunk` and `packed`, which shape its plan encoding and its
    executables (the reference backend ignores both, so they stay out of its
    key). `packed` is keyed on the *requested*
    spelling: ``"auto"`` and an explicit ``True`` may lower to the same
    encoding, but resolving it needs the schedule — too expensive for a
    cache lookup. `cache_dir` is not part of the key — it changes where a
    plan is stored, never what it is. Thread-safe: concurrent callers with
    the same key get the same engine object."""
    with span("planner.convert"):
        matrix = normalize_to_sell(
            matrix, slice_height=slice_height, width_multiple=width_multiple,
            validate=False,  # O(nnz) scan deferred to construction on a miss
        )
    resolved = resolve_backend(backend)
    if packed not in PACKED_CHOICES:
        raise ValueError(
            f"packed must be one of {PACKED_CHOICES}, got {packed!r}"
        )
    window_resolved = resolve_window(
        window,
        backend_resolved=resolved,
        cols_per_chunk=cols_per_chunk,
        slice_height=matrix.slice_height,
    )
    storage = resolve_value_dtype(value_dtype)
    block_rows = resolve_block_rows(
        block_rows, matrix, backend_resolved=resolved, window=window_resolved,
        value_dtype=storage,
    )
    key = (
        _sell_content_digest(matrix),
        window_resolved,
        block_rows,
        resolved,
        # Value storage changes numerics on every backend, so it keys both
        # ("native" and None share the engine — same resolution as __init__).
        storage,
        (cols_per_chunk, packed) if resolved == "pallas" else None,
    )
    adopted = None
    with _engine_lock:
        eng = _engine_cache.get(key)
        if eng is None:
            eng = SpMVEngine(
                matrix,
                window=window,
                block_rows=block_rows,
                backend=backend,
                cols_per_chunk=cols_per_chunk,
                packed=packed,
                value_dtype=value_dtype,
                cache_dir=cache_dir,
            )
            _engine_cache.put(key, eng)
        elif cache_dir is not None:
            # The cached engine may have been created without persistence (or
            # with a different directory). An explicit request must not be
            # silently dropped: adopt the directory and write through any plan
            # that was already built.
            eng.cache_dir = schedule_store.resolve_cache_dir(cache_dir)
            adopted = eng
    if adopted is not None:
        # npz write outside the global lock: the engine's own _plan_lock
        # guards it, so unrelated get_engine callers don't queue behind I/O.
        adopted.persist_schedule()
    return eng


def engine_cache_stats() -> Dict[str, int]:
    return {
        "size": len(_engine_cache),
        "hits": _engine_cache.hits,
        "misses": _engine_cache.misses,
    }
