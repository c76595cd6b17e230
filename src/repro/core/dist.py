"""Sharded multi-device SpMV: row-slice partitioning over a device mesh.

The paper's coalescer wins come from exploiting memory-level parallelism
across independent index windows (Sec. II-B); the scale-out of that idea is
to hand *disjoint groups of windows* to different memory systems. SparseP
(Giannoula et al., 2022) shows the 1D partitioning of the sparse matrix
across near-memory banks is the decisive design axis, and Serpens (Song et
al., 2022) earns its HBM bandwidth by striping sparse rows across channels.
`ShardedSpMVEngine` maps that decomposition onto a `jax.sharding` mesh:

  * **Row shards over the ``data`` axis.** The SELL matrix is partitioned by
    row-slices into contiguous shards. *Where* the boundaries fall is the
    ``partition`` strategy (`core.partition`): ``"even"`` splits by slice
    count (the legacy rule), ``"nnz"`` balances padded nonzeros, ``"cost"``
    (the ``"auto"`` default) balances a per-slice perfmodel cycle estimate
    — padded nnz + metadata bytes + estimated wide accesses — and
    ``"cost2d"`` refines that over a SparseP-style row x column-segment
    grid for extreme skew. Every shard pads to its *own* max slice width
    (not the global W), collapsing padded nnz on skewed shards; the
    reference executor's width reduction is a padding-invariant
    power-of-two tree (`engine._width_tree_sum`), so the decomposition
    stays numerically invisible (bit-identical on the reference backend for
    every strategy, pinned by tests).
  * **One plan per shard.** Each shard is a real `SELLMatrix` owned by a real
    `SpMVEngine`: its own padded plan, its own content-addressed
    `BlockSchedule` (the shard's index stream has its own digest), its own
    persistent npz file when a cache directory is configured — schedule
    digests and persistence compose per shard with zero new cache machinery.
  * **RHS columns over the ``model`` axis.** `matmat` splits the right-hand
    sides into balanced column groups; block (shard ``i``, column group
    ``j``) is dispatched on mesh device ``(i % data, j)`` via `jax.device_put`
    placement — JAX's async dispatch runs all blocks concurrently, the exact
    multi-device generalization of the engine's vmap-over-columns. ``x`` is
    replicated (the schedule-driven x-gather stays local to each shard's
    device, which is the point: the interesting communication is the
    broadcast of x, not the index traffic).

The mesh comes from `launch.mesh.make_host_mesh` by default, so the same
code path runs on a laptop CPU, a forced multi-device CPU
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — what tests and CI
use), and a TPU slice. More shards than mesh rows is allowed (shards
round-robin over the ``data`` axis), so shard-decomposition logic is
exercised even on a single device.

Execution entry point: `core/runtime.py`. Like `SpMVEngine`, this engine
implements the `runtime.Executor` protocol — ``stage`` places every
(row-shard, column-group) RHS block on its mesh device, ``dispatch``
launches all block matmats asynchronously, ``finalize`` gathers — and
``matmat`` *is* that three-step path run back to back. Wrap it in
`runtime.StreamingExecutor` to overlap the staging of the next RHS
micro-batch with compute on the previous one across the whole mesh.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import faults
from .coalescer import META_BYTES_PACKED, META_BYTES_UNPACKED, \
    coalesce_stats, schedule_meta_bytes
from .engine import DEFAULT_BUFFER_DEPTH, DEFAULT_COLS_PER_CHUNK, \
    DEFAULT_K_TILE, DEFAULT_WINDOW, get_engine, resolve_backend, \
    resolve_packed
from .formats import CSRMatrix, SELLMatrix
from .partition import resolve_partition, shard_bounds
from .perfmodel import matmat_spmv_perf, sharded_spmv_perf, \
    streaming_spmv_perf
from .runtime import column_groups, data_model_grid, device_put_rhs, \
    normalize_to_sell, proper_slice


def _default_mesh() -> jax.sharding.Mesh:
    """Host mesh over whatever devices exist (shared auto-factoring rule —
    local import keeps core importable without the launch package loaded)."""
    from repro.launch.mesh import auto_spmv_mesh

    return auto_spmv_mesh()


def device_str(dev: jax.Device) -> str:
    """Stable, JSON-serializable device name (``"cpu:0"``) — platform plus
    id. Raw `jax.Device` objects don't JSON-serialize, so `placement()`
    carries this alongside them for bench payloads and serving loops."""
    return f"{dev.platform}:{int(dev.id)}"


def row_shard_sells(
    sell: SELLMatrix,
    n_shards: int,
    *,
    partition: str = "even",
    window: Optional[int] = None,
    block_rows: int = 8,
    bounds: Optional[np.ndarray] = None,
) -> List[Tuple[SELLMatrix, int, int]]:
    """Partition a SELL matrix into `n_shards` contiguous row-slice shards.

    Returns ``[(shard_sell, row_lo, row_hi), ...]`` with ``row_lo/row_hi``
    the half-open global row range the shard owns. Boundaries come from the
    ``partition`` strategy (`core.partition.shard_bounds`; default
    ``"even"`` keeps the legacy slice-count split) or from an explicit
    ``bounds`` array (slice indices, ``n_shards + 1`` entries). Each shard
    pads to its *own* maximum slice width — padded nnz on narrow shards
    collapses instead of inheriting the global straggler width — and the
    reference executor's padding-invariant width reduction keeps per-row
    results bit-identical to the unsharded engine anyway.
    """
    from .spmv import _sell_padded  # local: spmv imports engine which is a sib

    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n_shards = min(n_shards, sell.n_slices) or 1
    if bounds is None:
        bounds, _ = shard_bounds(
            sell, n_shards, partition=partition,
            window=DEFAULT_WINDOW if window is None else int(window),
            block_rows=block_rows,
        )
    bounds = np.asarray(bounds, dtype=np.int64)
    n_shards = bounds.size - 1
    ci, va, _ = _sell_padded(sell)  # (n_slices, W, H)
    H = sell.slice_height
    widths = np.asarray(sell.slice_widths, dtype=np.int64)
    shards: List[Tuple[SELLMatrix, int, int]] = []
    for k in range(n_shards):
        s0, s1 = int(bounds[k]), int(bounds[k + 1])
        nsl = s1 - s0
        # A shard of empty slices keeps one zero column (colidx 0 / value 0)
        # so its engine still has a well-formed stream to plan against —
        # unless the whole matrix is width-0, which stays width-0.
        Ws = int(widths[s0:s1].max(initial=0))
        Ws = min(max(Ws, 1), ci.shape[1]) if ci.shape[1] else 0
        shard = SELLMatrix(
            n_rows=min(sell.n_rows, s1 * H) - s0 * H,
            n_cols=sell.n_cols,
            slice_height=H,
            slice_ptrs=np.arange(nsl + 1, dtype=np.int64) * (Ws * H),
            slice_widths=np.full(nsl, Ws, dtype=np.int32),
            colidx=np.ascontiguousarray(ci[s0:s1, :Ws].reshape(-1)),
            values=np.ascontiguousarray(va[s0:s1, :Ws].reshape(-1)),
        )
        shard.validate()
        shards.append((shard, s0 * H, min(sell.n_rows, s1 * H)))
    return shards


@dataclasses.dataclass
class _StagedRHS:
    """A RHS micro-batch placed on the mesh: one device array per
    (device-row, column-group), shared by every shard round-robined onto
    that row (the `stage` half of the Executor protocol)."""

    k: int
    groups: List[slice]
    placed: Dict[Tuple[int, int], jnp.ndarray]
    dtype: object


class _FailedShard:
    """Placeholder for a shard whose dispatch hit an injected fault.
    `finalize` recomputes the row-slice in degraded mode instead of
    gathering it."""

    __slots__ = ("error",)

    def __init__(self, error: faults.FaultInjected):
        self.error = error


@dataclasses.dataclass
class _PendingBlocks:
    """Dispatched-but-ungathered block results (the `dispatch` half).
    Carries k/dtype so `finalize` assembles the k=0 edge exactly like
    `matmat` does — the Executor identity holds for every input — and the
    staged RHS so degraded-mode recovery can recompute a failed shard's
    rows from source."""

    blocks: List[List[jnp.ndarray]]
    k: int
    dtype: object
    staged: Optional["_StagedRHS"] = None


class ShardedSpMVEngine:
    """Plan-once / execute-many SpMV sharded across a device mesh.

    ``matrix`` may be CSR (converted once, like `SpMVEngine`) or SELL.
    ``mesh`` must carry ``data`` and ``model`` axes (default: a host mesh
    over all visible devices via `launch.mesh.make_host_mesh`). Row shards
    map to the ``data`` axis, RHS column groups to the ``model`` axis.
    ``n_shards`` defaults to the ``data`` axis size; larger values
    round-robin shards over the mesh rows.

    ``partition`` selects where the shard boundaries fall
    (`core.partition`): ``"even"`` | ``"nnz"`` | ``"cost"`` | ``"cost2d"``,
    default ``"auto"`` -> ``"cost"`` (balance the per-slice perfmodel cycle
    estimate so no device straggles on skewed matrices).

    All plan parameters (``window``, ``block_rows``, ``backend``,
    ``cols_per_chunk``, ``k_tile``, ``matmat_mode``, ``packed``,
    ``buffer_depth``, ``value_dtype``, ``cache_dir``) are
    forwarded to every shard's `SpMVEngine`, so backends, window resolution,
    the fused multi-column matmat routing, the content-addressed schedule
    cache, and npz persistence all behave exactly as on the single-device
    engine — per shard (a pallas-backed sharded matmat streams each shard's
    schedule and values once per `k_tile` RHS columns on its own device).
    """

    def __init__(
        self,
        matrix: Union[CSRMatrix, SELLMatrix],
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        n_shards: Optional[int] = None,
        window: Optional[int] = None,
        block_rows: int = 8,
        slice_height: Optional[int] = None,
        width_multiple: int = 1,
        backend: str = "auto",
        cols_per_chunk: int = DEFAULT_COLS_PER_CHUNK,
        k_tile: int = DEFAULT_K_TILE,
        matmat_mode: str = "auto",
        packed: Union[bool, str] = "auto",
        buffer_depth: int = DEFAULT_BUFFER_DEPTH,
        value_dtype: Optional[str] = None,
        partition: str = "auto",
        cache_dir: Optional[str] = None,
    ):
        sell = normalize_to_sell(
            matrix, slice_height=slice_height, width_multiple=width_multiple
        )
        self.sell = sell
        self.mesh = mesh if mesh is not None else _default_mesh()
        # Device grid as (data, model), whatever the mesh's axis order.
        self.devices = data_model_grid(self.mesh)
        self.n_data, self.n_model = self.devices.shape

        self.backend = backend
        self.backend_resolved = resolve_backend(backend)
        self.block_rows = int(block_rows)
        self.window = window
        self.n_shards = (
            self.n_data if n_shards is None else int(n_shards)
        )
        if self.n_shards < 1:
            raise ValueError(
                f"n_shards must be >= 1, got {self.n_shards}"
            )
        # Partition strategy (core.partition): "auto" resolves to the
        # perfmodel cost balance; the boundary computation sees the same
        # window/block_rows geometry the shard plans will use.
        self.partition = partition
        self.partition_resolved = resolve_partition(partition)
        bounds, self._partition_info = shard_bounds(
            sell,
            min(self.n_shards, sell.n_slices) or 1,
            partition=partition,
            window=DEFAULT_WINDOW if window is None else int(window),
            block_rows=self.block_rows,
        )
        self._shards = row_shard_sells(sell, self.n_shards, bounds=bounds)
        self.n_shards = len(self._shards)  # clamped to n_slices
        # Through the engine cache: two sharded engines over the same matrix
        # (or a sharded engine rebuilt per request) share shard engines —
        # and therefore plans and compiled executables — by content digest.
        self.engines = [
            get_engine(
                shard,
                window=window,
                block_rows=block_rows,
                backend=backend,
                cols_per_chunk=cols_per_chunk,
                k_tile=k_tile,
                matmat_mode=matmat_mode,
                packed=packed,
                buffer_depth=buffer_depth,
                value_dtype=value_dtype,
                cache_dir=cache_dir,
            )
            for shard, _, _ in self._shards
        ]
        self.row_ranges = [(lo, hi) for _, lo, hi in self._shards]
        # Degraded-mode recovery log: one entry per shard recomputed via the
        # reference executor after an injected dispatch failure (see
        # `_recover_shard`); surfaced by `plan_report()["recovery"]`.
        self._recovery_events: List[Dict[str, object]] = []
        self._recovery_lock = threading.Lock()

    # -- placement ---------------------------------------------------------

    def _shard_device_row(self, i: int) -> int:
        return i % self.n_data

    def placement(self, k: int) -> List[Dict[str, object]]:
        """The (shard, column-group) -> device assignment `matmat(X)` with
        ``X.shape[1] == k`` will use. One entry per dispatched block; serving
        loops use this for per-device accounting. ``device`` is the raw
        `jax.Device`; ``device_str``/``device_id`` are its stable
        JSON-serializable forms (bench payloads dump placement directly).
        ``nnz_padded``/``width`` describe the shard's own padded footprint —
        per-shard width padding means these differ across shards on skewed
        matrices."""
        groups = column_groups(k, self.n_model)
        out: List[Dict[str, object]] = []
        for i, (lo, hi) in enumerate(self.row_ranges):
            shard_sell = self._shards[i][0]
            for j, cols in enumerate(groups):
                dev = self.devices[self._shard_device_row(i), j]
                out.append({
                    "shard": i,
                    "device": dev,
                    "device_str": device_str(dev),
                    "device_id": int(dev.id),
                    "rows": (lo, hi),
                    "cols": (cols.start, cols.stop),
                    "nnz_padded": int(shard_sell.nnz_padded),
                    "width": int(np.max(shard_sell.slice_widths, initial=0)),
                })
        return out

    # -- execution ---------------------------------------------------------

    @property
    def n_rows(self) -> int:
        return self.sell.n_rows

    @property
    def n_cols(self) -> int:
        return self.sell.n_cols

    def matvec(self, x: jnp.ndarray) -> np.ndarray:
        """y = A @ x: replicate x across the data axis, each shard computes
        its row block on its own device, concatenate. Returns the gathered
        result as a host array (re-uploading the assembled output to one
        device on every call would be pure wasted transfer — callers that
        want it on-device `device_put` it themselves)."""
        x = jnp.asarray(x)
        if x.ndim != 1 or x.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"matvec expects x of shape ({self.sell.n_cols},), got "
                f"{x.shape}"
            )
        placed: Dict[int, jnp.ndarray] = {}  # one x transfer per device row
        parts = []
        for i, eng in enumerate(self.engines):
            d = self._shard_device_row(i)
            if d not in placed:
                placed[d] = jax.device_put(x, self.devices[d, 0])
            parts.append(eng.matvec(placed[d]))
        # dispatched async; the host gather below synchronizes
        return np.concatenate([np.asarray(p) for p in parts])

    def matvec_parts(self, x: jnp.ndarray):
        """Per-shard matvec without the host gather: returns a list of
        ``(part, placed_x, (lo, hi))`` per row shard, where ``part`` is the
        shard's slice of ``A @ x`` (dispatched async on the shard's mesh
        device), ``placed_x`` is the replicated input committed to that
        device, and ``(lo, hi)`` the shard's global row range. Solver loops
        (core.solvers) use this to reduce dot products over the mesh
        ``data`` axis: each shard computes ``<x[lo:hi], part>`` on its own
        device and only the scalar partials meet on the host."""
        x = jnp.asarray(x)
        if x.ndim != 1 or x.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"matvec_parts expects x of shape ({self.sell.n_cols},), got "
                f"{x.shape}"
            )
        placed: Dict[int, jnp.ndarray] = {}  # one x transfer per device row
        out = []
        for i, eng in enumerate(self.engines):
            d = self._shard_device_row(i)
            if d not in placed:
                placed[d] = jax.device_put(x, self.devices[d, 0])
            out.append((eng.matvec(placed[d]), placed[d], self.row_ranges[i]))
        return out

    def matmat(self, X: jnp.ndarray) -> np.ndarray:
        """Y = A @ X with row shards on the ``data`` axis and RHS column
        groups on the ``model`` axis. Every (shard, column-group) block is
        dispatched before any result is gathered, so all mesh devices run
        concurrently. Bit-identical per column to the single-device engine
        on the reference backend. Returns the gathered result as a host
        array (see `matvec`). This is exactly the Executor pipeline run
        back to back: ``finalize(dispatch(stage(X)))``."""
        if not isinstance(X, (np.ndarray, jax.Array)):
            X = jnp.asarray(X)
        if X.ndim != 2 or X.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"matmat expects X of shape ({self.sell.n_cols}, k), got "
                f"{X.shape}"
            )
        return self.finalize(self.dispatch(self.stage(X)))

    def __call__(self, x: jnp.ndarray) -> np.ndarray:
        return self.matvec(x) if jnp.asarray(x).ndim == 1 else self.matmat(x)

    # -- streaming pipeline hooks (core.runtime.Executor protocol) ---------

    def stage(self, X: jnp.ndarray, *, donate: bool = False) -> _StagedRHS:
        """Place one RHS micro-batch on the mesh: one async `jax.device_put`
        per (device row, column group) — shards that round-robin onto the
        same mesh row share the placed block instead of re-sending identical
        host->device traffic per shard. Donation retires jax-array column
        blocks once transferred (see `runtime.device_put_rhs`)."""
        if X.ndim != 2 or X.shape[0] != self.sell.n_cols:
            raise ValueError(
                f"stage expects X of shape ({self.sell.n_cols}, k), got "
                f"{X.shape}"
            )
        k = int(X.shape[1])
        groups = column_groups(k, self.n_model)
        rows_used = {
            self._shard_device_row(i) for i in range(self.n_shards)
        }
        placed: Dict[Tuple[int, int], jnp.ndarray] = {}
        for d in sorted(rows_used):
            for j, cols in enumerate(groups):
                placed[(d, j)] = device_put_rhs(
                    X[:, cols], self.devices[d, j],
                    donate=donate and proper_slice(cols, k),
                )
        return _StagedRHS(k=k, groups=groups, placed=placed, dtype=X.dtype)

    def dispatch(self, staged: _StagedRHS) -> _PendingBlocks:
        """Launch every (row-shard, column-group) block matmat on its staged
        RHS — all async (JAX dispatch), no host synchronization.

        A shard whose dispatch hits an injected fault (the chaos harness's
        ``shard_fail`` site, `faults.FaultInjected`) does not poison the
        others: its slot carries a `_FailedShard` marker and `finalize`
        recomputes those rows in degraded mode. Any other error — a compile
        or device failure — propagates: recomputing it on the reference
        backend would hide a broken device path."""
        blocks: List[List[jnp.ndarray]] = []
        for i, eng in enumerate(self.engines):
            d = self._shard_device_row(i)
            try:
                faults.maybe_inject(
                    "shard_fail", f"injected dispatch failure on shard {i}"
                )
                blocks.append([
                    eng.matmat(staged.placed[(d, j)])
                    for j in range(len(staged.groups))
                ])
            except faults.FaultInjected as exc:
                blocks.append(_FailedShard(exc))
        return _PendingBlocks(
            blocks=blocks, k=staged.k, dtype=staged.dtype, staged=staged
        )

    def finalize(self, pending: _PendingBlocks) -> np.ndarray:
        """Gather all in-flight blocks (device->host copies synchronize) and
        assemble the (n_rows, k) result.

        Degraded mode: a shard marked failed at dispatch (an injected fault)
        has its row-slice recomputed via the *reference* executor on a
        surviving device; gather errors propagate. Per-shard planning makes the
        recompute bit-identical to the fault-free run on the reference
        backend (and within kernel parity tolerance of a pallas run); each
        recovery is logged in ``plan_report()["recovery"]``."""
        if pending.k == 0:  # no groups were dispatched; nothing to gather
            return np.zeros((self.sell.n_rows, 0), pending.dtype)
        rows = []
        for i, row in enumerate(pending.blocks):
            if isinstance(row, _FailedShard):
                rows.append(self._recover_shard(i, pending, row.error))
                continue
            rows.append(
                np.concatenate([np.asarray(b) for b in row], axis=1)
                if len(row) > 1 else np.asarray(row[0])
            )
        return np.concatenate(rows, axis=0)

    def _recover_shard(
        self, i: int, pending: _PendingBlocks, error: faults.FaultInjected
    ) -> np.ndarray:
        """Recompute shard *i*'s row block through the reference executor.

        The recovery engine shares the failed shard's SELL slice, geometry,
        and value dtype (all numerics-relevant knobs), so on the reference
        backend the recomputed rows are bit-identical to what the healthy
        dispatch would have produced — the reference executor's width
        reduction is padding-invariant, so even differing pad widths cannot
        perturb the sums. The recompute is dispatched on a surviving mesh
        row's device (the next row, when the mesh has more than one)."""
        if pending.staged is None:
            raise error
        staged = pending.staged
        d = self._shard_device_row(i)
        ref_eng = get_engine(
            self._shards[i][0],
            window=self.window,
            block_rows=self.block_rows,
            backend="reference",
            value_dtype=self.engines[i].value_dtype,
        )
        survivor = (d + 1) % self.n_data if self.n_data > 1 else d
        parts = []
        for j in range(len(staged.groups)):
            block = staged.placed[(d, j)]
            if self.n_data > 1:
                block = jax.device_put(block, self.devices[survivor, j])
            parts.append(np.asarray(ref_eng.matmat(block)))
        result = (
            np.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        )
        event = {
            "shard": i,
            "rows": self.row_ranges[i],
            "k": pending.k,
            "error": repr(error),
            "injected": True,
            "mode": "reference-recompute",
            "device_str": device_str(self.devices[survivor, 0]),
        }
        with self._recovery_lock:
            self._recovery_events.append(event)
        faults.note_recovered(error.site)
        return result

    # -- introspection / persistence ---------------------------------------

    def recovery_report(self) -> Dict[str, object]:
        """Degraded-mode recovery log: every shard row-slice recomputed via
        the reference executor after an injected dispatch failure."""
        with self._recovery_lock:
            events = [dict(e) for e in self._recovery_events]
        return {
            "events": events,
            "recovered": len(events),
            "injected": len(events),
        }

    def persist_schedules(self, cache_dir: Optional[str] = None) -> List[str]:
        """Write every shard's already-built schedule to the persistent
        store (see `SpMVEngine.persist_schedule`). Returns written paths."""
        paths = [eng.persist_schedule(cache_dir) for eng in self.engines]
        return [p for p in paths if p is not None]

    def plan_report(
        self, *, stream: Optional[Dict[str, int]] = None,
        k: Optional[int] = None,
    ) -> Dict[str, object]:
        """Aggregate plan report plus per-shard coalesce stats.

        Forces planning on every shard. ``shards[i]`` reports the rows the
        shard owns, its stream's wide-access count and coalesce rate, its
        schedule geometry, and whether its plan came out of the cache —
        the per-memory-bank view of the paper's Sec. II-B statistics.
        ``stream={"k": ..., "microbatch": ..., "depth": ...}`` adds the perf
        model's streamed-throughput prediction for the whole matrix under
        ``streaming``; ``k=`` adds the whole-matrix matmat amortization
        prediction under ``matmat`` (see `SpMVEngine.plan_report`).
        """
        shard_reports: List[Dict[str, object]] = []
        total_wide = 0
        total_elems = 0
        for i, eng in enumerate(self.engines):
            sched = eng.schedule  # force the plan
            _, _, shard_stream, _, _ = eng._ensure_plan()
            wide, rate = coalesce_stats(
                shard_stream, window=eng.window, block_rows=eng.block_rows
            )
            total_wide += wide
            total_elems += int(shard_stream.size)
            lo, hi = self.row_ranges[i]
            packed_eff = resolve_packed(eng.packed, sched)
            shard_reports.append({
                "shard": i,
                "rows": (lo, hi),
                "n_slices": eng.sell.n_slices,
                "nnz": int(np.count_nonzero(eng.sell.values)),
                "nnz_padded": eng.sell.nnz_padded,
                "width": int(np.max(eng.sell.slice_widths, initial=0)),
                "meta_bytes": schedule_meta_bytes(sched, packed=packed_eff),
                "meta_bytes_per_element": (
                    META_BYTES_PACKED if packed_eff else META_BYTES_UNPACKED
                ),
                "window": eng.window,
                "n_windows": sched.n_windows,
                "max_warps": sched.max_warps,
                "wide_accesses": wide,
                "coalesce_rate": rate,
                "schedule_cached": eng.plan_cached,
                "device_row": self._shard_device_row(i),
                "device_str": device_str(
                    self.devices[self._shard_device_row(i), 0]
                ),
            })
        streaming = None
        if stream is not None:
            streaming = {
                **{key: int(v) for key, v in stream.items()},
                "perf": {
                    system: dataclasses.asdict(
                        streaming_spmv_perf(self.sell, system, **stream)
                    )
                    for system in ("base", "pack256")
                },
            }
        matmat = None
        if k is not None:
            k_tile = self.engines[0].k_tile
            matmat = {
                "k": int(k),
                "k_tile": k_tile,
                "mode": self.engines[0].matmat_mode_resolved,
                "perf": {
                    system: dataclasses.asdict(
                        matmat_spmv_perf(self.sell, system, k=int(k),
                                         k_tile=k_tile)
                    )
                    for system in ("pack0", "pack256")
                },
            }
        # Straggler-bound sharded prediction over the *actual* shard
        # matrices (their own padded widths): max over per-shard cycles plus
        # the x broadcast — and the imbalance metric the partitioner
        # minimizes and the multi-device bench job gates.
        sharded_perf = sharded_spmv_perf(
            [s for s, _, _ in self._shards], "pack256"
        )
        partition_report = {
            **self._partition_info,
            "perf": dataclasses.asdict(sharded_perf),
            "imbalance": {
                "max_shard_cycles": sharded_perf.max_shard_cycles,
                "mean_shard_cycles": sharded_perf.mean_shard_cycles,
                "ratio": sharded_perf.imbalance,
            },
        }
        return {
            "n_rows": self.sell.n_rows,
            "n_cols": self.sell.n_cols,
            "nnz_padded": self.sell.nnz_padded,
            "backend": self.backend,
            "backend_resolved": self.backend_resolved,
            "mesh": {"data": self.n_data, "model": self.n_model},
            "n_devices": int(self.devices.size),
            "n_shards": self.n_shards,
            "block_rows": self.block_rows,
            "wide_accesses": total_wide,
            "coalesce_rate": (
                float(total_elems) / float(total_wide * self.block_rows)
                if total_wide else 0.0
            ),
            "partition": partition_report,
            "recovery": self.recovery_report(),
            "shards": shard_reports,
            **({"streaming": streaming} if streaming is not None else {}),
            **({"matmat": matmat} if matmat is not None else {}),
        }
