"""SELL SpMV Pallas kernel, fused with the coalesced indirect x-access.

Mirrors the paper's VPC pipeline (Sec. II-C) in a single kernel: each warp
of a window is one coalesced wide fetch of the dense vector x (one block of
`block_rows` entries per unique wide block per window), and the slices'
chunks are the VPC's VMAC consumption of SELL slices.

Two paths run it, picked by the layout the plan holds, which
`device_operands` chooses by `x_resident`. Where x fits VMEM the plan is
lane-dense: x is copied there once per product and one grid step covers a
tile of `TILE_SLICES` whole slices:
the chunks and warps of the tile are loops in the kernel body, and a warp's
wide fetch is a row load from VMEM plus an in-register lane gather. Every
other plan runs the per-warp grid (s, c, t), one step per (slice, chunk,
warp), with the warp's x block fetched by a data-dependent BlockSpec —
compute and the indirect stream overlap as prefetching overlaps compute in
the paper.

Layout: padded SELL (n_slices, W, H) with H = slice height (32), W padded to a
multiple of `cols_per_chunk`. One *window* of the indirect stream = one
(slice, chunk) = cols_per_chunk * H indices, matching the paper's windowed
coalescing of the column-index stream. The kernels stream each chunk's
values and metadata as one (1, window) row, and every block ends in full
array dims (the TPU's (8, 128) block-shape rule); a chunk's window element
``j * H + h`` folds into output row h.

`DevicePlan` is the kernel-ready, device-resident form of a `BlockSchedule`:
the SENTINEL-sanitized tag matrix plus the per-(slice, chunk) metadata words.
Building it per call would re-trace that preprocessing into every jit (and
re-upload it per trace), so plan-owning callers (`core.engine.SpMVEngine`)
build it **once** and share it between their matvec and matmat (the matvec
batched over right-hand sides). With a prebuilt plan the column-index
array itself is dead weight — the schedule already encodes every gather — so
`colidx` may be None and stays off the transfer path entirely.

**Packed metadata** is the bandwidth lever that lives here. The
per-element (warp id, row offset) pair is the kernel's indirect stream.
Both values are small — `elem_warp < max_warps` and `elem_offset <
block_rows`, each comfortably under 2**16 for every practical geometry — so
`build_device_plan(packed=...)` packs them into a single int32 word
``(warp << 16) | offset`` per trace element: 4 metadata bytes/element
instead of 8, the AXI-Pack move of narrowing the irregular stream to its
information content. A lossless unpacked fallback (two stacked int32 lanes)
is selected automatically when the geometry overflows the 16-bit halves;
the choice is recorded on the plan (`DevicePlan.packed`) and surfaced by
`SpMVEngine.plan_report()["metadata"]`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coalescer import (
    BlockSchedule,
    META_BYTES_PACKED,
    META_BYTES_UNPACKED,
    PACK_LIMIT,
    SENTINEL,
    packable_schedule,
    resolve_schedule,
)

#: Name of the kernel's instruction on a device trace (``sell_spmv.N``): the
#: `pallas_call` name and the innermost `jax.named_scope` around its call.
KERNEL_NAME = "sell_spmv"


@dataclasses.dataclass
class DevicePlan:
    """Kernel-ready coalescer plan: what both paths of the SELL kernel
    actually consume.

    tags:      (n_windows, max_warps) int32 — per-window wide-block ids with
               SENTINEL slots remapped to 0 (a SENTINEL tag is never hit by
               any `elem_warp`, so block 0 is a safe dummy fetch target and
               the scalar-prefetch index map needs no branch).
    elem_meta: the per-element indirect-stream words, already reshaped to the
               (slice, chunk) grid the kernels iterate, one row per word
               kind so each chunk's block ends in full array dims (the TPU
               block-shape rule) and lays out compactly in HBM.
               packed=True  -> (n_slices, n_chunks, 1, window) int32, each
                               word ``(elem_warp << 16) | elem_offset``
                               (4 metadata bytes/element);
               packed=False -> (n_slices, n_chunks, 2, window) int32, row 0
                               elem_warp, row 1 elem_offset (8 bytes/element,
                               the lossless fallback for geometries whose
                               warp ids or offsets overflow 16 bits).
               The x-resident matvec streams the same words lane-dense,
               as (n_windows * rows * window / 128, 128) (`lane_dense_plan`).
               The layout a plan holds picks the matvec's path
               (`lane_dense`).

    `elem_warp` / `elem_offset` remain available as decoding properties, so
    schedule-level invariants can be asserted against either encoding.

    The geometry ints and the `packed` flag ride in the pytree aux data, so a
    plan-carrying jit call specializes on them exactly like on static
    arguments.
    """

    tags: jnp.ndarray
    elem_meta: jnp.ndarray
    window: int
    block_rows: int
    cols_per_chunk: int
    slice_height: int
    n_slices: int
    n_chunks: int
    packed: bool

    @property
    def max_warps(self) -> int:
        return int(self.tags.shape[1])

    @property
    def lane_dense(self) -> bool:
        """Whether `elem_meta` is the x-resident path's lane-dense stream
        (`lane_dense_plan`) rather than chunk rows."""
        return self.elem_meta.ndim == 2

    @property
    def _chunk_meta(self) -> jnp.ndarray:
        """`elem_meta` as chunk rows, whichever layout the plan holds."""
        return self.elem_meta.reshape(
            self.n_slices, self.n_chunks, _meta_rows(self.packed), self.window
        )

    @property
    def elem_warp(self) -> jnp.ndarray:
        """(n_slices, n_chunks, window) int32 warp ids, whatever the encoding."""
        if self.packed:
            return jax.lax.shift_right_logical(self._chunk_meta[:, :, 0, :], 16)
        return self._chunk_meta[:, :, 0, :]

    @property
    def elem_offset(self) -> jnp.ndarray:
        """(n_slices, n_chunks, window) int32 offsets, whatever the encoding."""
        if self.packed:
            return jnp.bitwise_and(self._chunk_meta[:, :, 0, :], 0xFFFF)
        return self._chunk_meta[:, :, 1, :]

    @property
    def meta_bytes_per_element(self) -> int:
        return META_BYTES_PACKED if self.packed else META_BYTES_UNPACKED


jax.tree_util.register_pytree_node(
    DevicePlan,
    lambda p: (
        (p.tags, p.elem_meta),
        (p.window, p.block_rows, p.cols_per_chunk, p.slice_height,
         p.n_slices, p.n_chunks, p.packed),
    ),
    lambda aux, children: DevicePlan(*children, *aux),
)


def resolve_packing(packed: bool | str, schedule: BlockSchedule) -> bool:
    """Resolve a packing request against a schedule's geometry.

    ``"auto"`` packs whenever lossless (warp ids and offsets both fit 16
    bits); ``True`` demands packing and raises if the geometry overflows the
    narrow encoding; ``False`` always uses the int32 fallback."""
    if packed == "auto":
        return packable_schedule(schedule)
    if packed and not packable_schedule(schedule):
        raise ValueError(
            f"packed metadata needs elem_warp < {PACK_LIMIT} and "
            f"elem_offset < {PACK_LIMIT}, but the schedule has "
            f"max_warps={schedule.max_warps}, "
            f"block_rows={schedule.block_rows}; use packed='auto' to fall "
            f"back to the unpacked int32 encoding"
        )
    return bool(packed)


def build_device_plan(
    schedule: BlockSchedule,
    *,
    n_slices: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool | str = "auto",
) -> DevicePlan:
    """Lower a `BlockSchedule` to the device-resident `DevicePlan` the SELL
    kernel consumes. Validates that the schedule was built for exactly this
    (slice, chunk) geometry — a plan for different geometry would silently
    gather the wrong elements.

    `packed` selects the metadata encoding (see `resolve_packing`): the
    default ``"auto"`` packs (warp, offset) into one int32 word per element
    whenever that is lossless and falls back to two full words otherwise."""
    window = int(cols_per_chunk) * int(slice_height)
    if schedule.window != window:
        raise ValueError(
            f"schedule was planned for window={schedule.window}, but "
            f"cols_per_chunk={cols_per_chunk} x slice_height={slice_height} "
            f"needs window={window}"
        )
    if n_slices < 1 or schedule.n_windows % n_slices != 0:
        raise ValueError(
            f"schedule covers {schedule.n_windows} windows, which does not "
            f"tile {n_slices} slices"
        )
    n_chunks = schedule.n_windows // n_slices
    use_packed = resolve_packing(packed, schedule)
    ew = jnp.asarray(schedule.elem_warp, jnp.int32).reshape(
        n_slices, n_chunks, window
    )
    eo = jnp.asarray(schedule.elem_offset, jnp.int32).reshape(
        n_slices, n_chunks, window
    )
    if use_packed:
        # Both halves fit 16 bits; the shift may carry into the sign bit
        # (warp >= 2**15), which is why every decode site uses a *logical*
        # right shift.
        elem_meta = jnp.bitwise_or(jnp.left_shift(ew, 16), eo)[:, :, None, :]
    else:
        elem_meta = jnp.stack([ew, eo], axis=2)
    return DevicePlan(
        tags=jnp.where(schedule.tags == SENTINEL, 0, schedule.tags),
        elem_meta=elem_meta,
        window=window,
        block_rows=int(schedule.block_rows),
        cols_per_chunk=int(cols_per_chunk),
        slice_height=int(slice_height),
        n_slices=int(n_slices),
        n_chunks=int(n_chunks),
        packed=use_packed,
    )


def resolve_device_plan(
    colidx: jnp.ndarray | None,
    *,
    n_slices: int,
    W: int,
    slice_height: int,
    cols_per_chunk: int,
    block_rows: int,
    max_warps: int | None,
    schedule: BlockSchedule | None,
    plan: DevicePlan | None,
    packed: bool | str | None = None,
) -> DevicePlan:
    """Plan resolution for the SELL kernel: a prebuilt `plan` wins
    (validated against the call geometry), else a prebuilt `schedule` is
    lowered, else the plan is built from `colidx` (which is only then
    required). The geometry of record is the *values* array's — a `colidx`
    that disagrees with it (e.g. an unpadded index array next to
    width-padded values) must raise, not plan a schedule that indexes out
    of the grid. `packed` (None == "auto") picks the metadata encoding when
    the plan is built here; a prebuilt plan must already match it."""
    n_chunks = W // cols_per_chunk
    if colidx is not None and tuple(colidx.shape) != (
        n_slices, W, slice_height
    ):
        raise ValueError(
            f"colidx shape {tuple(colidx.shape)} disagrees with the values "
            f"geometry ({n_slices}, {W}, {slice_height}); pad colidx and "
            f"values together (core.runtime.pad_width)"
        )
    if plan is not None:
        if (
            plan.n_slices != n_slices
            or plan.n_chunks != n_chunks
            or plan.slice_height != slice_height
            or plan.cols_per_chunk != cols_per_chunk
        ):
            raise ValueError(
                f"device plan was built for (n_slices={plan.n_slices}, "
                f"n_chunks={plan.n_chunks}, cols_per_chunk="
                f"{plan.cols_per_chunk}, slice_height={plan.slice_height}), "
                f"call expects (n_slices={n_slices}, n_chunks={n_chunks}, "
                f"cols_per_chunk={cols_per_chunk}, "
                f"slice_height={slice_height})"
            )
        if plan.block_rows != block_rows:
            raise ValueError(
                f"device plan was built for block_rows={plan.block_rows}, "
                f"call expects block_rows={block_rows}"
            )
        if packed not in (None, "auto") and bool(packed) != plan.packed:
            raise ValueError(
                f"device plan was built with packed={plan.packed}, call "
                f"expects packed={bool(packed)}; rebuild the plan "
                f"(build_device_plan) to change the metadata encoding"
            )
        return plan
    if schedule is None:
        if colidx is None:
            raise ValueError(
                "colidx is required to build a plan; pass schedule= or "
                "plan= to run without the column-index array"
            )
        schedule, _ = resolve_schedule(
            colidx.reshape(-1),
            window=cols_per_chunk * slice_height,
            block_rows=block_rows,
            max_warps=max_warps,
        )
    else:
        expected = n_slices * n_chunks
        if schedule.n_windows != expected:
            raise ValueError(
                f"schedule covers {schedule.n_windows} windows but this "
                f"geometry has {expected}"
            )
        if schedule.block_rows != block_rows:
            raise ValueError(
                f"schedule was planned for block_rows={schedule.block_rows}, "
                f"call expects block_rows={block_rows}"
            )
    return build_device_plan(
        schedule,
        n_slices=n_slices,
        cols_per_chunk=cols_per_chunk,
        slice_height=slice_height,
        packed="auto" if packed is None else packed,
    )


def _decode_meta(meta, *, packed: bool):
    """Split one chunk's metadata into (elem_warp, elem_offset), each
    (1, window) int32.

    `meta` is (1, window) int32 when packed, (2, window) int32 otherwise. The
    packed decode must be a *logical* shift: warp ids >= 2**15 set the int32
    sign bit and an arithmetic shift would smear it."""
    if packed:
        ew = jax.lax.shift_right_logical(meta, 16)
        eo = jnp.bitwise_and(meta, 0xFFFF)
    else:
        ew = meta[0:1]
        eo = meta[1:2]
    return ew, eo


def _meta_rows(packed: bool) -> int:
    """Rows per chunk of `DevicePlan.elem_meta`: one packed word, or the
    (warp, offset) pair."""
    return 1 if packed else 2


#: Bytes of scalar-prefetched tag rows one kernel call may hold. The tags sit
#: in SMEM (1 MiB per TPU v5e core) with every row padded to 128 lanes, so a
#: plan larger than this runs as several calls over groups of slices.
SMEM_TAG_BUDGET = 512 * 1024


def slices_per_call(n_slices: int, n_chunks: int, max_warps: int) -> int:
    """Slices one kernel call covers so that its tag rows fit
    `SMEM_TAG_BUDGET`."""
    row_bytes = 4 * 128 * -(-max(int(max_warps), 1) // 128)
    fit = SMEM_TAG_BUDGET // (row_bytes * n_chunks)
    return max(1, min(int(n_slices), fit))


def grid_steps(plan: DevicePlan) -> int:
    """Kernel grid steps one `sell_spmv_pallas` product over `plan` runs:
    one per tile of slices on the x-resident path (a lane-dense plan), else
    every slice group's (group, n_chunks, max_warps) grid, the last group
    counted whole although it overlaps its predecessor."""
    if plan.lane_dense:
        return -(-plan.n_slices // _tile(plan.n_slices))
    group = slices_per_call(plan.n_slices, plan.n_chunks, plan.max_warps)
    n_groups = -(-plan.n_slices // group)
    return n_groups * group * plan.n_chunks * plan.max_warps


def lane_gathers(plan: DevicePlan) -> int:
    """Lane-gather rounds one product over `plan` runs on the x-resident
    path: every window loops over its tag row `warps_per_gather` warps at a
    time. 0 on the per-warp grid, which gathers no lanes."""
    if not plan.lane_dense:
        return 0
    rounds = -(-plan.max_warps // warps_per_gather(plan.window))
    return plan.n_slices * plan.n_chunks * rounds


def run_slice_groups(call, tags, *, n_slices: int, n_chunks: int,
                     group: int):
    """Run ``call(base, tags_group)`` over consecutive groups of `group`
    slices and stitch the per-slice outputs back together.

    ``base`` is a (1,) int32 array holding the group's first slice, passed to
    the kernel as a second scalar-prefetch operand; ``tags_group`` holds the
    group's ``group * n_chunks`` tag rows. ``call`` returns an array whose
    leading axis is the group's `group` slices. The last group is shifted back
    to end at ``n_slices`` (it overlaps its predecessor rather than reading
    past the plan), and only its new slices are kept."""
    n_groups = -(-n_slices // group)

    def one(g):
        base = jnp.minimum(g * group, n_slices - group).astype(jnp.int32)
        tags_g = jax.lax.dynamic_slice_in_dim(
            tags, base * n_chunks, group * n_chunks
        )
        return call(base.reshape(1), tags_g)

    if n_groups == 1:
        return one(jnp.int32(0))
    out = jax.lax.map(one, jnp.arange(n_groups, dtype=jnp.int32))
    head = out[:-1].reshape(-1, *out.shape[2:])
    return jnp.concatenate([head, out[-1][n_groups * group - n_slices:]])


def _gather_x(ew, eo, t, x_row, block_rows: int):
    """(1, window) x values for the elements warp `t` serves: element i takes
    ``x_row[eo[i]]`` where ``ew[i] == t`` and 0 elsewhere. The
    response-splitter + element-packer as `block_rows` selects, so the f32
    values pass through unrounded."""
    hit = ew == t
    g = jnp.zeros(ew.shape, x_row.dtype)
    for r in range(block_rows):
        g = jnp.where(hit & (eo == r), x_row[:, r:r + 1], g)
    return g


def _sum_chunk_columns(prod, cols_per_chunk: int, slice_height: int):
    """Reduce a (rows, window) chunk over its `cols_per_chunk` columns (the
    VPC VMAC's reduction): window element ``j * H + h`` folds into lane h."""
    acc = prod[:, :slice_height]
    for j in range(1, cols_per_chunk):
        acc = acc + prod[:, j * slice_height:(j + 1) * slice_height]
    return acc


def _chunk_contribution(ew, eo, t, vals, x_row, out_dtype, *,
                        block_rows: int, cols_per_chunk: int,
                        slice_height: int):
    """(1, H) contribution of warp `t` to a slice's output: gather, multiply
    by the nonzeros and reduce over the chunk's columns (the VPC VMAC), in
    at least f32 whatever the storage dtypes."""
    cdt = jnp.promote_types(out_dtype, jnp.float32)
    g = _gather_x(ew, eo, t, x_row, block_rows)
    prod = vals.astype(cdt) * g.astype(cdt)
    return _sum_chunk_columns(prod, cols_per_chunk, slice_height).astype(
        out_dtype
    )


def _kernel(
    tags_ref,  # scalar-prefetch (group * n_chunks, max_warps)
    base_ref,  # scalar-prefetch (1,): the group's first slice
    elem_meta_ref,  # (1, 1, 1 | 2, window)
    values_ref,  # (1, 1, 1, window)
    x_block_ref,  # (1, 1, block_rows) — coalesced wide fetch of x
    out_ref,  # (1, 1, H)
    *,
    block_rows: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool,
):
    c = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when((c == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ew, eo = _decode_meta(elem_meta_ref[0, 0], packed=packed)
    out_ref[0] += _chunk_contribution(
        ew, eo, t, values_ref[0, 0], x_block_ref[0], out_ref.dtype,
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=slice_height,
    )


def _meta_block_spec(window: int, packed: bool):
    """BlockSpec for one chunk's metadata on the per-warp grid (s, c, t);
    the map offsets the slice by the call's group base."""
    return pl.BlockSpec(
        (1, 1, _meta_rows(packed), window),
        lambda s, c, t, tags, base: (base[0] + s, c, 0, 0),
    )


def chunk_values(values: jnp.ndarray, cols_per_chunk: int) -> jnp.ndarray:
    """(n_slices, W, H) SELL values as one (1, window) row per (slice, chunk)
    — the layout the per-warp grid streams. Plan-owning callers store values
    in this shape so no call pays a relayout."""
    n_slices, W, H = values.shape
    return values.reshape(n_slices, W // cols_per_chunk, 1, cols_per_chunk * H)


# -- the x-resident matvec ---------------------------------------------------
#
# One grid step per tile of `TILE_SLICES` whole slices. x sits in VMEM for the
# whole product, viewed as (ceil(n_cols / 128), 128): a coalesced block
# ``tag`` of `block_rows` floats is x row ``(tag * block_rows) // 128`` from
# lane ``(tag * block_rows) % 128`` on. Inside a step a loop runs over the
# tile's windows, and the warps of a window are unrolled: each warp's x row
# is loaded from VMEM and its elements picked out by an in-register lane
# gather, several warps to a vector register.
# The stream is lane-dense: values and metadata as (rows, 128) arrays holding
# each window's ``window // 128`` lane rows in order (`stream_values`,
# `lane_dense_plan`).

#: Lanes of a TPU vector register: the width of x rows and stream rows.
LANES = 128

#: Bytes of padded x the resident path may hold in VMEM: half of a TPU v5e
#: core's 128 MiB, so about 16 M float32 columns.
X_RESIDENT_BUDGET = 64 * 1024 * 1024

#: Slices per grid step of the resident path.
TILE_SLICES = 8

#: VMEM the resident kernel may use beyond x and its pipelined blocks.
_VMEM_HEADROOM = 8 * 1024 * 1024


def stream_values(values: jnp.ndarray) -> jnp.ndarray:
    """SELL values, (n_slices, W, H), as the resident path's lane-dense
    (n_slices * W * H / 128, 128) stream: window after window, each window's
    elements in order."""
    return values.reshape(-1, LANES)


def lane_dense_plan(plan: DevicePlan) -> DevicePlan:
    """`plan` with its metadata as the resident path's lane-dense
    (n_windows * rows * window / 128, 128) stream (rows: `_meta_rows`)."""
    return dataclasses.replace(plan, elem_meta=plan.elem_meta.reshape(-1, LANES))


def _x_rows(n_cols: int) -> int:
    return -(-int(n_cols) // LANES)


def _tile(n_slices: int) -> int:
    return min(TILE_SLICES, int(n_slices))


def _tag_block_bytes(plan: DevicePlan) -> int:
    """SMEM of one step's tag rows, each padded to 128 lanes, double
    buffered."""
    row_bytes = 4 * LANES * -(-plan.max_warps // LANES)
    return 2 * _tile(plan.n_slices) * plan.n_chunks * row_bytes


def resident_geometry(n_cols: int, value_dtype, *, window: int,
                      slice_height: int) -> bool:
    """The conditions of `x_resident` that a schedule does not decide: x of
    `n_cols` entries padded to whole 128-lane rows fits `X_RESIDENT_BUDGET`,
    values are 32-bit (a window's lane rows then load from any row), a
    window is whole lane rows and a slice's outputs divide a lane row. A
    planner that finds them true coalesces at `LANES` (`SpMVEngine`)."""
    return (
        _x_rows(n_cols) * LANES * 4 <= X_RESIDENT_BUDGET
        and jnp.dtype(value_dtype).itemsize == 4
        and window % LANES == 0
        and LANES % slice_height == 0
    )


def x_resident(plan: DevicePlan, n_cols: int, value_dtype) -> bool:
    """Whether a product over `plan` with an x of `n_cols` entries and
    values of `value_dtype` runs the x-resident path: `resident_geometry`
    holds, a coalesced block divides a lane row, and a step's tag rows fit
    `SMEM_TAG_BUDGET`. Any other plan runs the per-warp grid."""
    return (
        resident_geometry(n_cols, value_dtype, window=plan.window,
                          slice_height=plan.slice_height)
        and LANES % plan.block_rows == 0
        and _tag_block_bytes(plan) <= SMEM_TAG_BUDGET
    )


def device_operands(plan: DevicePlan, values: jnp.ndarray, n_cols: int):
    """``(values, plan)`` as a plan-owning caller holds them for repeated
    products with an x of `n_cols` entries: lane-dense where `x_resident`
    holds, else the per-warp grid's chunk rows. `sell_spmv_pallas` takes
    its path from the plan's layout, so the stream is never relaid out by a
    call. `values` is (n_slices, W, H); `plan` holds chunk rows, as
    `build_device_plan` makes it."""
    if x_resident(plan, n_cols, values.dtype):
        return stream_values(values), lane_dense_plan(plan)
    return chunk_values(values, plan.cols_per_chunk), plan


def warps_per_gather(window: int) -> int:
    """Warps one lane gather of the resident kernel serves: a window's
    ``window // 128`` lane rows fill that many of a vector register's 8
    sublanes, and each further copy of the window serves one more warp."""
    return max(1, 8 // (window // LANES))


def _fold_lane_rows(prod, cols_per_chunk: int, slice_height: int):
    """`_sum_chunk_columns` on a window held as (window / 128, 128) lane
    rows: window element ``j * H + h`` folds into lane h, j in order."""
    acc = None
    for j in range(cols_per_chunk):
        r, lane = divmod(j * slice_height, LANES)
        part = prod[r:r + 1, lane:lane + slice_height]
        acc = part if acc is None else acc + part
    return acc


def _resident_kernel(
    tags_ref,  # SMEM (tile * n_chunks, max_warps): this step's tag rows
    meta_ref,  # (tile * n_chunks * rows * window / 128, 128) int32
    values_ref,  # (tile * n_chunks * window / 128, 128)
    x_hbm,  # (ceil(n_cols / 128), 128), ANY memory space
    out_ref,  # (tile, H)
    x_vmem,  # VMEM scratch, x's shape: x for the whole product
    sem,  # DMA semaphore of x's copy
    *,
    n_slices: int,
    tile: int,
    n_chunks: int,
    max_warps: int,
    block_rows: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load_x():
        # The grid runs in order ("arbitrary"), so x stays for every step.
        copy = pltpu.make_async_copy(x_hbm, x_vmem, sem)
        copy.start()
        copy.wait()

    rows = (cols_per_chunk * slice_height) // LANES  # lane rows per window
    meta_rows = _meta_rows(packed) * rows
    cdt = x_vmem.dtype
    out_dtype = out_ref.dtype
    # block_rows divides 128, so both are powers of two: shifts and masks.
    per_row = LANES // block_rows
    row_shift = per_row.bit_length() - 1
    lane_shift = block_rows.bit_length() - 1
    # A window's rows fill `rows` of a vector register's 8 sublanes, so one
    # register serves `n_warps` warps at once: copy k of the window serves
    # warp t0 + k, and one lane gather picks all of them.
    n_warps = warps_per_gather(cols_per_chunk * slice_height)
    copy_of = jax.lax.broadcasted_iota(
        jnp.int32, (n_warps * rows, LANES), 0) // rows

    def gather_x(w, ew, eo):
        """(rows, 128) x values of window `w`'s elements: element i takes
        lane ``eo[i]`` of its warp's coalesced block."""
        eo_n = jnp.concatenate([eo] * n_warps, axis=0)
        ew_n = jnp.concatenate([ew] * n_warps, axis=0) - copy_of
        g = jnp.zeros(copy_of.shape, cdt)
        for t0 in range(0, max_warps, n_warps):
            for k in range(n_warps):
                # Past max_warps no element matches; any tag row will do.
                tag = tags_ref[w, min(t0 + k, max_warps - 1)]
                row = x_vmem[pl.ds(
                    jax.lax.shift_right_logical(tag, row_shift), 1), :]
                x_n = (jnp.broadcast_to(row, copy_of.shape) if k == 0
                       else jnp.where(copy_of == k, row, x_n))
                if per_row > 1:
                    tag_n = (jnp.full(copy_of.shape, tag) if k == 0
                             else jnp.where(copy_of == k, tag, tag_n))
            lanes = eo_n
            if per_row > 1:
                # The lane bases on the vector side: the scalar unit is the
                # busiest in this loop. A block of a whole lane row starts
                # at lane 0, so it needs none.
                lanes = eo_n + jax.lax.shift_left(
                    jnp.bitwise_and(tag_n, per_row - 1), lane_shift)
            picked = jnp.take_along_axis(x_n, lanes, axis=1,
                                         mode="promise_in_bounds")
            g = jnp.where(ew_n == t0, picked, g)
        # Each element matched in exactly one copy; the others hold zeros.
        out = g[:rows]
        for k in range(1, n_warps):
            out = out + g[k * rows:(k + 1) * rows]
        return out

    def window(w, acc):
        vals = values_ref[pl.ds(w * rows, rows), :]
        meta = meta_ref[pl.ds(w * meta_rows, meta_rows), :]
        if packed:
            ew = jax.lax.shift_right_logical(meta, 16)
            eo = jnp.bitwise_and(meta, 0xFFFF)
        else:
            ew, eo = meta[:rows], meta[rows:]
        return acc + vals.astype(cdt) * gather_x(w, ew, eo)

    def one_slice(s, carry):
        # The slice's chunks are summed lane by lane, then folded once.
        acc = jax.lax.fori_loop(
            s * n_chunks, (s + 1) * n_chunks, window,
            jnp.zeros((rows, LANES), cdt),
        )
        out_ref[pl.ds(s, 1), :] = _fold_lane_rows(
            acc, cols_per_chunk, slice_height).astype(out_dtype)
        return carry

    # The last tile may hold fewer slices; its other output rows are clipped.
    jax.lax.fori_loop(0, jnp.minimum(tile, n_slices - i * tile), one_slice, 0)


def _sell_spmv_resident(dplan: DevicePlan, values, x, *, interpret: bool):
    """y = A @ x by the x-resident path (see `x_resident`): one kernel call,
    `grid_steps` = ceil(n_slices / `TILE_SLICES`)."""
    n_slices, n_chunks = dplan.n_slices, dplan.n_chunks
    H = dplan.slice_height
    rows = dplan.window // LANES
    meta_rows = _meta_rows(dplan.packed) * rows
    tile = _tile(n_slices)
    out_dtype = jnp.promote_types(values.dtype, x.dtype)
    cdt = jnp.promote_types(out_dtype, jnp.float32)
    n_x = _x_rows(x.shape[0])
    x_p = jnp.pad(x.astype(cdt), (0, n_x * LANES - x.shape[0])).reshape(
        n_x, LANES)
    vals = stream_values(values)
    if vals.dtype.itemsize != 4:
        # A lane-dense plan's values come narrower only when the caller
        # casts them per call (to an x of another dtype); the kernel loads
        # one lane row at a time, which takes 32-bit rows.
        vals = vals.astype(cdt)
    meta = dplan.elem_meta
    tw = tile * n_chunks  # windows per step
    block_bytes = 2 * (
        tw * rows * LANES * vals.dtype.itemsize + tw * meta_rows * LANES * 4
        + tile * LANES * 4
    )
    x_bytes = n_x * LANES * jnp.dtype(cdt).itemsize
    kernel = functools.partial(
        _resident_kernel, n_slices=n_slices, tile=tile, n_chunks=n_chunks,
        max_warps=dplan.max_warps, block_rows=dplan.block_rows,
        cols_per_chunk=dplan.cols_per_chunk, slice_height=H,
        packed=dplan.packed,
    )
    call = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n_slices, tile),),
        in_specs=[
            pl.BlockSpec((tw, dplan.max_warps), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tw * meta_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tw * rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_slices, H), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((n_x, LANES), cdt),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=x_bytes + block_bytes + _VMEM_HEADROOM,
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )
    # The kernel copies x in from HBM itself, which a batched call cannot
    # (memory space ANY): under vmap (`SpMVEngine.matmat`) each
    # right-hand side runs as a product of its own, in a loop.
    @jax.custom_batching.sequential_vmap
    def product(tags, meta, vals, x_p):
        with jax.named_scope(KERNEL_NAME):
            return call(tags, meta, vals, x_p)

    return product(dplan.tags, meta, vals, x_p).reshape(-1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cols_per_chunk", "block_rows", "max_warps", "packed", "interpret",
    ),
)
def sell_spmv_pallas(
    colidx: jnp.ndarray | None,  # (n_slices, W, H) int32, or None with a plan
    values: jnp.ndarray,  # (n_slices, W, H) (W % cols_per_chunk == 0)
    x: jnp.ndarray,  # (n_cols,)
    *,
    cols_per_chunk: int = 8,
    block_rows: int = 8,
    max_warps: int | None = None,
    schedule: BlockSchedule | None = None,
    plan: DevicePlan | None = None,
    packed: bool | str | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y = A @ x, y: (n_slices * H,). Semantics: ref.sell_spmv_ref.

    A prebuilt `schedule` (from core.engine.cached_block_schedule) or — better
    for repeat execution — a prebuilt `plan` (`build_device_plan`) skips
    per-call plan construction; with either, `colidx` may be None (the plan
    already encodes the whole indirect stream, so the index array never
    touches the dispatch path).

    `packed` picks the metadata encoding when the plan is built here
    (None == "auto": one int32 word per element whenever lossless);
    The plan's layout picks the path. A lane-dense plan
    (`device_operands`, or a plan built here where `x_resident` holds) runs
    one call of the x-resident kernel (`_sell_spmv_resident`). A plan of
    chunk rows runs the per-warp grid, whose BlockSpecs pipeline each
    chunk's values and metadata and each warp's x block; plans whose tags
    exceed `SMEM_TAG_BUDGET` run as one kernel call per group of slices."""
    n_slices, W, H = values.shape
    if W % cols_per_chunk != 0:
        raise ValueError(
            f"sell_spmv consumes SELL in chunks of {cols_per_chunk} columns "
            f"but the padded width is {W}; plan width-aware — pad W to a "
            f"multiple of cols_per_chunk (core.engine.SpMVEngine with "
            f"backend='pallas' does this at planning time)"
        )
    n_chunks = W // cols_per_chunk
    window = cols_per_chunk * H
    # The indirect stream in storage order: slice-by-slice, column-major.
    dplan = resolve_device_plan(
        colidx, n_slices=n_slices, W=W, slice_height=H,
        cols_per_chunk=cols_per_chunk, block_rows=block_rows,
        max_warps=max_warps, schedule=schedule, plan=plan, packed=packed,
    )
    if plan is None and x_resident(dplan, x.shape[0], values.dtype):
        dplan = lane_dense_plan(dplan)
    if dplan.lane_dense:
        return _sell_spmv_resident(dplan, values, x, interpret=interpret)
    vals = chunk_values(values, cols_per_chunk)

    R = x.shape[0]
    n_blocks = -(-R // block_rows)
    x_p = jnp.pad(x, (0, n_blocks * block_rows - R)).reshape(
        n_blocks, 1, block_rows
    )

    def tag_of(s, c, t, tags, base):
        return (tags[s * n_chunks + c, t], 0, 0)

    group = slices_per_call(n_slices, n_chunks, dplan.max_warps)
    out_shape = jax.ShapeDtypeStruct(
        # Accumulate in the promoted dtype (bf16 values x f32 input -> f32
        # accumulation), matching ref.sell_spmv_ref's natural promotion.
        (group, 1, H), jnp.promote_types(values.dtype, x.dtype)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(group, n_chunks, dplan.max_warps),
        in_specs=[
            _meta_block_spec(window, dplan.packed),
            pl.BlockSpec(
                (1, 1, 1, window),
                lambda s, c, t, tags, base: (base[0] + s, c, 0, 0),
            ),
            pl.BlockSpec((1, 1, block_rows), tag_of),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, H), lambda s, c, t, tags, base: (s, 0, 0)
        ),
    )
    kernel = functools.partial(
        _kernel, block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=H, packed=dplan.packed,
    )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name=KERNEL_NAME,
    )

    def one_call(base, tags):
        with jax.named_scope(KERNEL_NAME):
            return call(tags, base, dplan.elem_meta, vals, x_p)

    out = run_slice_groups(
        one_call, dplan.tags, n_slices=n_slices, n_chunks=n_chunks,
        group=group,
    )
    return out.reshape(-1)
