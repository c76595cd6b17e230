"""Block-coalesced gather — Pallas TPU kernel (the paper's adapter, TPU-native).

Mechanism mapping (see DESIGN.md §2):
  * The coalescer's *request warps* become the kernel grid's inner dimension:
    grid step (w, t) fetches wide block `tags[w, t]` of the table from HBM
    into VMEM once — one wide access per unique block per window, exactly the
    CSHR policy's access count.
  * The CSHR *Hitmap* is the vectorized mask `elem_warp == t`; the *Offsets*
    are `elem_offset`. Extraction + response-splitting + element packing
    (paper Fig. 2b return path) collapse into ONE one-hot matmul on the MXU,
    with the one-hot built transposed from the (1, window) metadata row:
        out[window] += onehot_t(hitmap, offsets)^T @ table_block
    which restores original request order for free.
  * The index-side "parallel indexing" is the vectorized schedule construction
    in core.coalescer.build_block_schedule (all N lanes at once).

The table block is (block_rows, D): `block_rows * D * itemsize` plays the role
of the 512 b DRAM access granularity. The table is viewed as
(n_blocks, block_rows, D) so every block ends in full array dims, which the
TPU's block-shape rule accepts for any block_rows (paged KV uses 1).

Like the SELL kernels, this kernel consumes a `DevicePlan` — the gather
geometry is the degenerate SELL one (`n_slices = n_windows`, one chunk of
`cols_per_chunk=1` x `slice_height=window` per window), so the same packed
``(warp << 16) | offset`` metadata words and SENTINEL-sanitized tags flow
through unchanged. Plan-owning callers (`core.gather_engine.GatherEngine`)
build the plan **once** (`build_gather_plan`) and pass it per call; with a
prebuilt plan the index array is dead weight (`indices=None`, the schedule
already encodes every gather) and only `n_out` is needed to trim the padded
output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coalescer import BlockSchedule, resolve_schedule

from .sell_spmv import (
    DevicePlan,
    _decode_meta,
    _meta_rows,
    build_device_plan,
    run_slice_groups,
    slices_per_call,
)

#: Name of the kernel's instruction on a device trace
#: (``coalesced_gather.N``), as in `kernels.sell_spmv.KERNEL_NAME`.
KERNEL_NAME = "coalesced_gather"


def build_gather_plan(
    schedule: BlockSchedule, *, packed: bool | str = "auto"
) -> DevicePlan:
    """Lower a flat-stream `BlockSchedule` to the gather kernel's `DevicePlan`.

    The gather grid is (window, warp) with no slice/chunk tiling, so the plan
    geometry is one chunk per window: ``n_slices = n_windows``,
    ``cols_per_chunk = 1``, ``slice_height = window``."""
    return build_device_plan(
        schedule,
        n_slices=schedule.n_windows,
        cols_per_chunk=1,
        slice_height=schedule.window,
        packed=packed,
    )


def resolve_gather_plan(
    indices: jnp.ndarray | None,
    *,
    window: int,
    block_rows: int,
    max_warps: int | None = None,
    schedule: BlockSchedule | None = None,
    plan: DevicePlan | None = None,
    packed: bool | str | None = None,
) -> DevicePlan:
    """Shared plan resolution for the gather kernel, mirroring
    `sell_spmv.resolve_device_plan`: a prebuilt `plan` wins (validated
    against the call geometry), else a prebuilt `schedule` is lowered, else
    the plan is built from `indices` (only then required)."""
    if plan is not None:
        if (
            plan.window != window
            or plan.cols_per_chunk != 1
            or plan.n_chunks != 1
        ):
            raise ValueError(
                f"gather plan was built for (window={plan.window}, "
                f"cols_per_chunk={plan.cols_per_chunk}, "
                f"n_chunks={plan.n_chunks}), call expects window={window} "
                f"with the gather geometry (cols_per_chunk=1, n_chunks=1); "
                f"rebuild with build_gather_plan"
            )
        if plan.block_rows != block_rows:
            raise ValueError(
                f"gather plan was built for block_rows={plan.block_rows}, "
                f"call expects block_rows={block_rows}"
            )
        if packed not in (None, "auto") and bool(packed) != plan.packed:
            raise ValueError(
                f"gather plan was built with packed={plan.packed}, call "
                f"expects packed={bool(packed)}; rebuild the plan to change "
                f"the metadata encoding"
            )
        return plan
    if indices is not None:
        sched, _ = resolve_schedule(
            indices.reshape(-1), window=window, block_rows=block_rows,
            max_warps=max_warps, schedule=schedule,
        )
    elif schedule is not None:
        # No stream to length-check against; geometry must still agree.
        if schedule.window != window or schedule.block_rows != block_rows:
            raise ValueError(
                f"schedule was planned for (window={schedule.window}, "
                f"block_rows={schedule.block_rows}), call expects "
                f"(window={window}, block_rows={block_rows})"
            )
        sched = schedule
    else:
        raise ValueError(
            "indices are required to build a plan; pass schedule= or plan= "
            "to run without the index array"
        )
    return build_gather_plan(sched, packed="auto" if packed is None else packed)


def _kernel(
    tags_ref,  # scalar-prefetch: (group, max_warps) int32 (sentinel->0)
    base_ref,  # scalar-prefetch (1,): the group's first window
    elem_meta_ref,  # (1, 1, 1 | 2, window)
    table_block_ref,  # (1, block_rows, D) — the coalesced wide fetch
    out_ref,  # (window, D)
    *,
    block_rows: int,
    window: int,
    packed: bool,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ew, eo = _decode_meta(elem_meta_ref[0, 0], packed=packed)
    # Hitmap x Offsets -> one-hot extraction matrix for this request warp,
    # built transposed (block_rows, window) from the (1, window) metadata row
    # and contracted over the block-row axis.
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, window), 0)
    onehot_t = ((ew == t) & (eo == rows)).astype(jnp.float32)
    # Extract in f32: each output row receives exactly one table row, so the
    # cast back to the table dtype is exact.
    out_ref[...] += jax.lax.dot_general(
        onehot_t, table_block_ref[0].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "window", "block_rows", "max_warps", "packed", "n_out", "interpret",
    ),
)
def coalesced_gather_pallas(
    table: jnp.ndarray,
    indices: jnp.ndarray | None = None,
    *,
    window: int = 256,
    block_rows: int = 8,
    max_warps: int | None = None,
    schedule: BlockSchedule | None = None,
    plan: DevicePlan | None = None,
    packed: bool | str | None = None,
    n_out: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather `table[indices]` through the coalesced data path.

    table: (R, D); indices: (n,) int32. Returns (n, D) in `table.dtype`
    (accumulation exact: each output row receives exactly one block row).

    max_warps bounds unique blocks per window (defaults to the always-safe
    `window`); smaller values shrink the grid when the caller knows the
    stream's locality (asserted at schedule build when indices are concrete).

    A prebuilt `schedule` (core.engine.cached_block_schedule) skips per-call
    plan construction; a prebuilt `plan` (`build_gather_plan`) additionally
    skips the schedule->plan lowering, and then `indices` may be None —
    `n_out` (default: the plan's padded length) trims the output. Plans whose
    tags exceed the SMEM budget run as one call per group of windows, like
    the SELL kernels."""
    R, D = table.shape
    dplan = resolve_gather_plan(
        indices, window=window, block_rows=block_rows, max_warps=max_warps,
        schedule=schedule, plan=plan, packed=packed,
    )
    n_windows = dplan.n_slices
    if n_out is None:
        n_out = indices.shape[0] if indices is not None else n_windows * window
    if not 0 <= n_out <= n_windows * window:
        raise ValueError(
            f"n_out={n_out} does not fit the plan's {n_windows} windows of "
            f"{window} ({n_windows * window} padded elements)"
        )
    # Pad table to whole blocks, one (block_rows, D) block per leading index
    # so each block ends in full array dims (any block_rows, 1 included).
    n_blocks = -(-R // block_rows)
    table_p = jnp.pad(table, ((0, n_blocks * block_rows - R), (0, 0))).reshape(
        n_blocks, block_rows, D
    )

    group = slices_per_call(n_windows, 1, dplan.max_warps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(group, dplan.max_warps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, _meta_rows(dplan.packed), window),
                lambda w, t, tags, base: (base[0] + w, 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, block_rows, D),
                lambda w, t, tags, base: (tags[w, t], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((window, D), lambda w, t, tags, base: (w, 0)),
    )
    call = pl.pallas_call(
        functools.partial(
            _kernel, block_rows=block_rows, window=window, packed=dplan.packed
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((group * window, D), table.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )

    def one_call(base, tags):
        with jax.named_scope(KERNEL_NAME):
            out = call(tags, base, dplan.elem_meta, table_p)
        return out.reshape(group, window, D)

    out = run_slice_groups(
        one_call, dplan.tags, n_slices=n_windows, n_chunks=1, group=group,
    )
    return out.reshape(n_windows * window, D)[:n_out]
