"""SELL SpMV Pallas kernel, fused with the coalesced indirect x-access.

Mirrors the paper's VPC pipeline (Sec. II-C) in a single kernel: the grid's
inner `t` dimension performs the adapter's coalesced wide fetches of the dense
vector x (one VMEM block per unique wide block per window), and the (s, c)
dimensions perform the VPC's VMAC consumption of SELL slices — compute and the
indirect stream overlap exactly as prefetching overlaps compute in the paper.

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
build it **once** and share it between the matvec kernel here and the fused
matmat kernel (`kernels.sell_spmm`). With a prebuilt plan the column-index
array itself is dead weight — the schedule already encodes every gather — so
`colidx` may be None and stays off the transfer path entirely.

Two bandwidth levers live here (the ROADMAP "bandwidth roofline push"):

* **Packed metadata.** The per-element (warp id, row offset) pair is the
  kernel's indirect stream. Both values are small — `elem_warp <
  max_warps` and `elem_offset < block_rows`, each comfortably under 2**16
  for every practical geometry — so `build_device_plan(packed=...)` packs
  them into a single int32 word ``(warp << 16) | offset`` per trace
  element: 4 metadata bytes/element instead of 8, the AXI-Pack move of
  narrowing the irregular stream to its information content. A lossless
  unpacked fallback (two stacked int32 lanes) is selected automatically
  when the geometry overflows the 16-bit halves; the choice is recorded
  on the plan (`DevicePlan.packed`) and surfaced by
  `SpMVEngine.plan_report()["metadata"]`.

* **Double-buffered chunk pipelining.** With ``buffer_depth >= 2`` the
  kernels stream SELL values + metadata through a rotating VMEM scratch
  with explicit async copies: while chunk g computes out of slot
  ``g % depth``, the DMA for chunk ``g + depth - 1`` fills the next slot —
  the in-kernel analog of the host-side `StreamingExecutor` pipeline
  (and of the paper's prefetch-overlaps-compute VPC timing).
  ``buffer_depth=1`` keeps the classic BlockSpec-pipelined path.
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

#: Default VMEM pipeline depth for both SELL kernels: double buffering.
DEFAULT_BUFFER_DEPTH = 2

#: Upper bound on the manual VMEM pipeline depth (slots are real VMEM).
MAX_BUFFER_DEPTH = 8


@dataclasses.dataclass
class DevicePlan:
    """Kernel-ready coalescer plan: what both SELL kernels actually consume.

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
    def elem_warp(self) -> jnp.ndarray:
        """(n_slices, n_chunks, window) int32 warp ids, whatever the encoding."""
        if self.packed:
            return jax.lax.shift_right_logical(self.elem_meta[:, :, 0, :], 16)
        return self.elem_meta[:, :, 0, :]

    @property
    def elem_offset(self) -> jnp.ndarray:
        """(n_slices, n_chunks, window) int32 offsets, whatever the encoding."""
        if self.packed:
            return jnp.bitwise_and(self.elem_meta[:, :, 0, :], 0xFFFF)
        return self.elem_meta[:, :, 1, :]

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
    """Lower a `BlockSchedule` to the device-resident `DevicePlan` both SELL
    kernels consume. Validates that the schedule was built for exactly this
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
    """Shared plan resolution for both SELL kernels: a prebuilt `plan` wins
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


def _validate_buffer_depth(buffer_depth: int) -> int:
    depth = int(buffer_depth)
    if not 1 <= depth <= MAX_BUFFER_DEPTH:
        raise ValueError(
            f"buffer_depth must be in [1, {MAX_BUFFER_DEPTH}] (1 = classic "
            f"BlockSpec pipeline, >= 2 = manual double buffering), got "
            f"{buffer_depth}"
        )
    return depth


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


def _kernel_buffered(
    tags_ref,  # scalar-prefetch (group * n_chunks, max_warps)
    base_ref,  # scalar-prefetch (1,): the group's first slice
    elem_meta_hbm,  # full meta array, ANY memory space
    values_hbm,  # full (n_slices, n_chunks, 1, window) values, ANY space
    x_block_ref,  # (1, 1, block_rows) — coalesced wide fetch of x
    out_ref,  # (1, 1, H)
    meta_vmem,  # (depth, 1 | 2, window) scratch
    vals_vmem,  # (depth, 1, window) scratch
    sems,  # DMA semaphores (2, depth)
    *,
    block_rows: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool,
    n_chunks: int,
    total_chunks: int,
    depth: int,
):
    """Double-buffered variant: SELL values + metadata stream through a
    rotating `depth`-slot VMEM scratch with explicit async copies, so the DMA
    for chunk ``g + depth - 1`` overlaps the compute of chunk ``g`` (the
    kernel-level analog of the host-side StreamingExecutor pipeline). Scratch
    persists across sequential grid steps; x keeps its scalar-prefetch
    BlockSpec and is pipelined by pallas as before."""
    s = pl.program_id(0)
    c = pl.program_id(1)
    t = pl.program_id(2)
    g = s * n_chunks + c  # linearized chunk index across this call's slices

    def chunk_dma(gg, slot):
        s_g = base_ref[0] + gg // n_chunks
        c_g = gg % n_chunks
        return (
            pltpu.make_async_copy(
                elem_meta_hbm.at[s_g, c_g], meta_vmem.at[slot],
                sems.at[0, slot],
            ),
            pltpu.make_async_copy(
                values_hbm.at[s_g, c_g], vals_vmem.at[slot], sems.at[1, slot],
            ),
        )

    @pl.when((s == 0) & (c == 0) & (t == 0))
    def _warm_up():
        # Fill the first depth-1 slots before any compute waits on them.
        for j in range(min(depth - 1, total_chunks)):
            for cp in chunk_dma(j, j):
                cp.start()

    @pl.when((c == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    slot = jax.lax.rem(g, depth)

    @pl.when(t == 0)
    def _stage():
        look_ahead = g + depth - 1

        @pl.when(look_ahead < total_chunks)
        def _prefetch():
            # Slot (g - 1) % depth: its chunk finished computing last step.
            for cp in chunk_dma(look_ahead, jax.lax.rem(look_ahead, depth)):
                cp.start()

        for cp in chunk_dma(g, slot):
            cp.wait()

    ew, eo = _decode_meta(meta_vmem[slot], packed=packed)
    out_ref[0] += _chunk_contribution(
        ew, eo, t, vals_vmem[slot], x_block_ref[0], out_ref.dtype,
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=slice_height,
    )


def _meta_block_spec(window: int, packed: bool, rank: int):
    """BlockSpec for one chunk's metadata in the depth-1 path. `rank` is the
    number of leading grid axes in the index map signature (2 for spmv's
    (s, c, t), 3 for spmm's (s, q, c, t)); the map offsets the slice by the
    call's group base."""
    block = (1, 1, _meta_rows(packed), window)
    if rank == 2:
        return pl.BlockSpec(
            block, lambda s, c, t, tags, base: (base[0] + s, c, 0, 0)
        )
    return pl.BlockSpec(
        block, lambda s, q, c, t, tags, base: (base[0] + s, c, 0, 0)
    )


def chunk_values(values: jnp.ndarray, cols_per_chunk: int) -> jnp.ndarray:
    """(n_slices, W, H) SELL values as one (1, window) row per (slice, chunk)
    — the layout both SELL kernels stream. Plan-owning callers store values
    in this shape so no call pays a relayout."""
    n_slices, W, H = values.shape
    return values.reshape(n_slices, W // cols_per_chunk, 1, cols_per_chunk * H)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cols_per_chunk", "block_rows", "max_warps", "packed",
        "buffer_depth", "interpret",
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
    buffer_depth: int = DEFAULT_BUFFER_DEPTH,
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
    `buffer_depth >= 2` streams values + metadata through a rotating VMEM
    scratch with async copies (see `_kernel_buffered`), `buffer_depth=1`
    keeps the classic BlockSpec pipeline. Plans whose tags exceed
    `SMEM_TAG_BUDGET` run as one kernel call per group of slices."""
    n_slices, W, H = values.shape
    if W % cols_per_chunk != 0:
        raise ValueError(
            f"sell_spmv consumes SELL in chunks of {cols_per_chunk} columns "
            f"but the padded width is {W}; plan width-aware — pad W to a "
            f"multiple of cols_per_chunk (core.engine.SpMVEngine with "
            f"backend='pallas' does this at planning time)"
        )
    depth = _validate_buffer_depth(buffer_depth)
    n_chunks = W // cols_per_chunk
    window = cols_per_chunk * H
    # The indirect stream in storage order: slice-by-slice, column-major.
    dplan = resolve_device_plan(
        colidx, n_slices=n_slices, W=W, slice_height=H,
        cols_per_chunk=cols_per_chunk, block_rows=block_rows,
        max_warps=max_warps, schedule=schedule, plan=plan, packed=packed,
    )
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
    out_spec = pl.BlockSpec((1, 1, H), lambda s, c, t, tags, base: (s, 0, 0))
    x_spec = pl.BlockSpec((1, 1, block_rows), tag_of)
    common = dict(
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=H, packed=dplan.packed,
    )
    if depth == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(group, n_chunks, dplan.max_warps),
            in_specs=[
                _meta_block_spec(window, dplan.packed, rank=2),
                pl.BlockSpec(
                    (1, 1, 1, window),
                    lambda s, c, t, tags, base: (base[0] + s, c, 0, 0),
                ),
                x_spec,
            ],
            out_specs=out_spec,
        )
        kernel = functools.partial(_kernel, **common)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(group, n_chunks, dplan.max_warps),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                x_spec,
            ],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((depth, _meta_rows(dplan.packed), window),
                           jnp.int32),
                pltpu.VMEM((depth, 1, window), values.dtype),
                pltpu.SemaphoreType.DMA((2, depth)),
            ],
        )
        kernel = functools.partial(
            _kernel_buffered, **common,
            n_chunks=n_chunks, total_chunks=group * n_chunks, depth=depth,
        )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )
    out = run_slice_groups(
        lambda base, tags: call(tags, base, dplan.elem_meta, vals, x_p),
        dplan.tags, n_slices=n_slices, n_chunks=n_chunks, group=group,
    )
    return out.reshape(-1)
