"""Fused multi-column SELL SpMM Pallas kernel: one indirect stream, k columns.

The paper's coalescer wins by paying for each wide indirect fetch once and
reusing it across the window (Sec. II-C). `sell_spmv` applies that *within*
one right-hand side; this kernel applies the same reuse argument *across* the
RHS batch: instead of re-running the coalesced x-gather and re-streaming the
schedule metadata and SELL values once per column (what vmapping the matvec
kernel does), each warp's wide fetch grabs a ``(block_rows, k_tile)`` tile of
the dense X and the extraction becomes a real MXU matmul: the one-hot is
built transposed and weighted by the chunk's values, folded over the chunk's
columns into ``m (block_rows, H)``, and

    m^T (H, block_rows) @ X_block (block_rows, k_tile) -> (H, k_tile)

so the metadata stream and the SELL values are read **once per k_tile
columns** instead of once per column — HBM SpMV designs (Serpens) and the
SSSR sparse-dense argument get their bandwidth efficiency from exactly this
amortization. A fourth grid dimension tiles wide RHS batches into
``k_tile``-column passes; ``k_tile`` is clamped to k so narrow batches never
pay padding compute.

Grid: ``(n_slices, n_ktiles, n_chunks, max_warps)`` — for a fixed (slice,
k-tile) output block the (chunk, warp) dimensions iterate innermost, so the
``(H, k_tile)`` accumulator stays resident exactly like the matvec kernel's
``(H,)`` accumulator does.

The matvec kernel's two bandwidth levers apply unchanged (see
`kernels.sell_spmv`): plans may carry **packed** one-word-per-element
metadata, and ``buffer_depth >= 2`` streams SELL values + metadata through a
rotating VMEM scratch with explicit async copies so the next chunk's DMA
overlaps this chunk's MXU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coalescer import BlockSchedule

from .sell_spmv import (
    DEFAULT_BUFFER_DEPTH,
    DevicePlan,
    _decode_meta,
    _meta_block_spec,
    _meta_rows,
    _sum_chunk_columns,
    _validate_buffer_depth,
    chunk_values,
    resolve_device_plan,
    run_slice_groups,
    slices_per_call,
)


def _accumulate(ew, eo, t, vals, x_block, out_ref, *, block_rows: int,
                cols_per_chunk: int, slice_height: int):
    """One warp's contribution to the (H, k_tile) output tile.

    The one-hot is built transposed, ``(block_rows, window)``, from the
    (1, window) metadata row and weighted by the chunk's values; folding the
    chunk's columns gives ``m (block_rows, H)``, and one MXU matmul
    contracting the block-row axis applies it to the whole RHS tile:
    ``out (H, k_tile) += m^T @ X_block``, in at least f32 (the MXU
    accumulates in 32 bits) whatever the storage dtypes."""
    acc = jnp.promote_types(out_ref.dtype, jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, ew.shape[1]), 0)
    w = jnp.where((ew == t) & (eo == rows), vals.astype(acc), 0)
    m = _sum_chunk_columns(w, cols_per_chunk, slice_height)
    out_ref[0, 0] += jax.lax.dot_general(
        m, x_block.astype(acc), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc,
    ).astype(out_ref.dtype)


def _kernel(
    tags_ref,  # scalar-prefetch (group * n_chunks, max_warps)
    base_ref,  # scalar-prefetch (1,): the group's first slice
    elem_meta_ref,  # (1, 1, 1 | 2, window)
    values_ref,  # (1, 1, 1, window)
    x_block_ref,  # (1, 1, block_rows, k_tile) — coalesced wide fetch of X
    out_ref,  # (1, 1, H, k_tile)
    *,
    block_rows: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool,
):
    c = pl.program_id(2)
    t = pl.program_id(3)

    @pl.when((c == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ew, eo = _decode_meta(elem_meta_ref[0, 0], packed=packed)
    # Extraction across the whole RHS tile: the wide fetch is amortized over
    # k_tile columns.
    _accumulate(
        ew, eo, t, values_ref[0, 0], x_block_ref[0, 0], out_ref,
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=slice_height,
    )


def _kernel_buffered(
    tags_ref,  # scalar-prefetch (group * n_chunks, max_warps)
    base_ref,  # scalar-prefetch (1,): the group's first slice
    elem_meta_hbm,  # full meta array, ANY memory space
    values_hbm,  # full (n_slices, n_chunks, 1, window) values, ANY space
    x_block_ref,  # (1, 1, block_rows, k_tile)
    out_ref,  # (1, 1, H, k_tile)
    meta_vmem,  # (depth, 1 | 2, window) scratch
    vals_vmem,  # (depth, 1, window) scratch
    sems,  # DMA semaphores (2, depth)
    *,
    block_rows: int,
    cols_per_chunk: int,
    slice_height: int,
    packed: bool,
    n_chunks: int,
    n_ktiles: int,
    total_chunks: int,
    depth: int,
):
    """Double-buffered variant of the fused kernel: chunk passes are
    linearized over (slice, k-tile, chunk) and their values + metadata stream
    through a rotating `depth`-slot VMEM scratch, so the DMA for pass
    ``g + depth - 1`` overlaps the MXU work of pass ``g``. X keeps its
    scalar-prefetch BlockSpec exactly like the matvec kernel."""
    s = pl.program_id(0)
    q = pl.program_id(1)
    c = pl.program_id(2)
    t = pl.program_id(3)
    g = (s * n_ktiles + q) * n_chunks + c  # linearized chunk pass

    def chunk_dma(gg, slot):
        c_g = gg % n_chunks
        s_g = base_ref[0] + (gg // n_chunks) // n_ktiles
        return (
            pltpu.make_async_copy(
                elem_meta_hbm.at[s_g, c_g], meta_vmem.at[slot],
                sems.at[0, slot],
            ),
            pltpu.make_async_copy(
                values_hbm.at[s_g, c_g], vals_vmem.at[slot], sems.at[1, slot],
            ),
        )

    @pl.when((s == 0) & (q == 0) & (c == 0) & (t == 0))
    def _warm_up():
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
            for cp in chunk_dma(look_ahead, jax.lax.rem(look_ahead, depth)):
                cp.start()

        for cp in chunk_dma(g, slot):
            cp.wait()

    ew, eo = _decode_meta(meta_vmem[slot], packed=packed)
    _accumulate(
        ew, eo, t, vals_vmem[slot], x_block_ref[0, 0], out_ref,
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=slice_height,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "cols_per_chunk", "block_rows", "k_tile", "max_warps", "packed",
        "buffer_depth", "interpret",
    ),
)
def sell_spmm_pallas(
    colidx: jnp.ndarray | None,  # (n_slices, W, H) int32, or None with a plan
    values: jnp.ndarray,  # (n_slices, W, H) (W % cols_per_chunk == 0)
    X: jnp.ndarray,  # (n_cols, k)
    *,
    cols_per_chunk: int = 8,
    block_rows: int = 8,
    k_tile: int = 8,
    max_warps: int | None = None,
    schedule: BlockSchedule | None = None,
    plan: DevicePlan | None = None,
    packed: bool | str | None = None,
    buffer_depth: int = DEFAULT_BUFFER_DEPTH,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns Y = A @ X, Y: (n_slices * H, k). Semantics: ref.sell_spmm_ref
    (bit-compatible per column with sell_spmv up to summation order).

    One pass over the schedule metadata and the SELL values serves ``k_tile``
    RHS columns; ``k`` is padded up to a multiple of the (clamped) tile with
    zero columns and the padding is sliced off before returning. The same
    prebuilt `schedule`/`plan` objects the matvec kernel takes are accepted —
    `core.engine.SpMVEngine` shares one `DevicePlan` between both kernels —
    and with either, `colidx` may be None (it never touches the dispatch
    path). `packed`, `buffer_depth` and the split into calls over groups of
    slices behave exactly as in `sell_spmv_pallas`."""
    n_slices, W, H = values.shape
    if X.ndim != 2:
        raise ValueError(f"sell_spmm expects X of shape (n_cols, k), got "
                         f"{X.shape}")
    if W % cols_per_chunk != 0:
        raise ValueError(
            f"sell_spmm consumes SELL in chunks of {cols_per_chunk} columns "
            f"but the padded width is {W}; plan width-aware — pad W to a "
            f"multiple of cols_per_chunk (core.engine.SpMVEngine with "
            f"backend='pallas' does this at planning time)"
        )
    if k_tile < 1:
        raise ValueError(f"k_tile must be >= 1, got {k_tile}")
    depth = _validate_buffer_depth(buffer_depth)
    k = int(X.shape[1])
    out_dtype = jnp.promote_types(values.dtype, X.dtype)
    if k == 0:
        return jnp.zeros((n_slices * H, 0), out_dtype)
    n_chunks = W // cols_per_chunk
    window = cols_per_chunk * H
    dplan = resolve_device_plan(
        colidx, n_slices=n_slices, W=W, slice_height=H,
        cols_per_chunk=cols_per_chunk, block_rows=block_rows,
        max_warps=max_warps, schedule=schedule, plan=plan, packed=packed,
    )
    vals = chunk_values(values, cols_per_chunk)

    # Clamp the tile to k (a 1-column batch must not pay k_tile columns of
    # MXU work), then pad k up to a whole number of tiles with zero columns.
    kt = min(int(k_tile), k)
    n_ktiles = -(-k // kt)
    k_pad = n_ktiles * kt
    R = X.shape[0]
    n_blocks = -(-R // block_rows)
    # One (block_rows, kt) tile per (wide block, k-tile): each block ends in
    # full array dims, as TPU block shapes must.
    X_p = jnp.pad(
        X, ((0, n_blocks * block_rows - R), (0, k_pad - k))
    ).reshape(n_blocks, block_rows, n_ktiles, kt).transpose(0, 2, 1, 3)

    def tag_of(s, q, c, t, tags, base):
        return (tags[s * n_chunks + c, t], q, 0, 0)

    group = slices_per_call(n_slices, n_chunks, dplan.max_warps)
    # Accumulate in the promoted dtype (bf16 values x f32 RHS -> f32
    # accumulation), matching ref.sell_spmm_ref's natural promotion.
    out_shape = jax.ShapeDtypeStruct((group, n_ktiles, H, kt), out_dtype)
    out_spec = pl.BlockSpec(
        (1, 1, H, kt), lambda s, q, c, t, tags, base: (s, q, 0, 0)
    )
    x_spec = pl.BlockSpec((1, 1, block_rows, kt), tag_of)
    common = dict(
        block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=H, packed=dplan.packed,
    )
    if depth == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(group, n_ktiles, n_chunks, dplan.max_warps),
            in_specs=[
                _meta_block_spec(window, dplan.packed, rank=3),
                pl.BlockSpec(
                    (1, 1, 1, window),
                    lambda s, q, c, t, tags, base: (base[0] + s, c, 0, 0),
                ),
                x_spec,
            ],
            out_specs=out_spec,
        )
        kernel = functools.partial(_kernel, **common)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(group, n_ktiles, n_chunks, dplan.max_warps),
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
            n_chunks=n_chunks, n_ktiles=n_ktiles,
            total_chunks=group * n_ktiles * n_chunks, depth=depth,
        )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )
    out = run_slice_groups(
        lambda base, tags: call(tags, base, dplan.elem_meta, vals, X_p),
        dplan.tags, n_slices=n_slices, n_chunks=n_chunks, group=group,
    )  # (n_slices, n_ktiles, H, kt)
    return out.transpose(0, 2, 1, 3).reshape(n_slices * H, k_pad)[:, :k]
