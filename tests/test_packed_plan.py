"""Packed-metadata DevicePlans: lossless 16/16-bit round-trips, the int32
overflow fallback, packed-vs-unpacked kernel parity on both plan layouts,
and the engine-cache identity of the packing knob."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core.coalescer import (
    META_BYTES_PACKED,
    META_BYTES_UNPACKED,
    PACK_LIMIT,
    build_block_schedule,
    packable_schedule,
    schedule_meta_bytes,
)
from repro.core.engine import clear_engine_cache, get_engine
from repro.core.formats import csr_to_sell, dense_to_csr
from repro.core.matrices import banded
from repro.kernels import ops, ref
from repro.kernels.sell_spmv import (
    DevicePlan,
    build_device_plan,
    lane_dense_plan,
    resolve_packing,
)

RNG = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _fresh_engine_cache():
    clear_engine_cache()
    yield


def _schedule(stream, *, window, block_rows):
    return build_block_schedule(
        jnp.asarray(stream, jnp.int32), window=window, block_rows=block_rows
    )


# -- pack/unpack round trip -------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    n_slices=st.integers(1, 5),
    cpc=st.sampled_from([3, 4, 5, 8]),  # odd chunk widths included
    H=st.sampled_from([7, 8, 16]),  # odd slice heights included
    n_chunks=st.integers(1, 4),
    block_rows=st.sampled_from([2, 8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_roundtrip_bit_exact(
    n_slices, cpc, H, n_chunks, block_rows, seed
):
    """The packed plan's decoded (warp, offset) arrays are bit-identical to
    the schedule's own across odd widths and W % cols_per_chunk != 0
    geometries (the stream length is whatever n_chunks windows hold)."""
    rng = np.random.default_rng(seed)
    window = cpc * H
    W = n_chunks * cpc
    stream = rng.integers(0, 10_000, size=n_slices * n_chunks * window)
    sched = _schedule(stream, window=window, block_rows=block_rows)
    plan = build_device_plan(
        sched, n_slices=n_slices, cols_per_chunk=cpc, slice_height=H,
        packed=True,
    )
    assert plan.packed and W % cpc == 0
    shape = (n_slices, n_chunks, window)
    np.testing.assert_array_equal(
        np.asarray(plan.elem_warp),
        np.asarray(sched.elem_warp, np.int32).reshape(shape),
    )
    np.testing.assert_array_equal(
        np.asarray(plan.elem_offset),
        np.asarray(sched.elem_offset, np.int32).reshape(shape),
    )
    # the unpacked fallback decodes to the same arrays
    unpacked = build_device_plan(
        sched, n_slices=n_slices, cols_per_chunk=cpc, slice_height=H,
        packed=False,
    )
    np.testing.assert_array_equal(
        np.asarray(plan.elem_warp), np.asarray(unpacked.elem_warp)
    )
    np.testing.assert_array_equal(
        np.asarray(plan.elem_offset), np.asarray(unpacked.elem_offset)
    )
    assert plan.meta_bytes_per_element == META_BYTES_PACKED
    assert unpacked.meta_bytes_per_element == META_BYTES_UNPACKED


@pytest.mark.parametrize("packed", [True, False])
def test_lane_dense_plan_decodes_like_chunk_rows(packed):
    """The x-resident path's lane-dense metadata holds the same words: it
    decodes to the schedule's warps and offsets, and reshapes back to the
    chunk rows."""
    n_slices, n_chunks, cpc, H = 3, 2, 8, 32
    stream = RNG.integers(0, 5_000, size=n_slices * n_chunks * cpc * H)
    sched = _schedule(stream, window=cpc * H, block_rows=8)
    plan = build_device_plan(sched, n_slices=n_slices, cols_per_chunk=cpc,
                             slice_height=H, packed=packed)
    lane = lane_dense_plan(plan)
    rows = 1 if packed else 2
    assert lane.elem_meta.shape == (n_slices * n_chunks * rows * 2, 128)
    for name in ("elem_warp", "elem_offset"):
        np.testing.assert_array_equal(np.asarray(getattr(lane, name)),
                                      np.asarray(getattr(plan, name)))
    np.testing.assert_array_equal(np.asarray(lane._chunk_meta),
                                  np.asarray(plan.elem_meta))


def test_pack_decodes_high_warp_ids_with_logical_shift():
    """Warp ids >= 2**15 set the int32 sign bit after the shift; an
    arithmetic right shift would smear it into garbage. The decode must use
    a logical shift — exercised here at the 16-bit extremes."""
    ew = np.array([0, 1, 2**15, PACK_LIMIT - 1], np.int32)
    eo = np.array([0, PACK_LIMIT - 1, 5, PACK_LIMIT - 1], np.int32)
    meta = jnp.asarray((ew.astype(np.int64) << 16) | eo, jnp.int32)
    plan = DevicePlan(
        tags=jnp.zeros((4, 1), jnp.int32),
        elem_meta=meta.reshape(1, 1, 1, 4),
        window=4, block_rows=PACK_LIMIT, cols_per_chunk=1, slice_height=4,
        n_slices=1, n_chunks=1, packed=True,
    )
    np.testing.assert_array_equal(
        np.asarray(plan.elem_warp).ravel(), ew
    )
    np.testing.assert_array_equal(
        np.asarray(plan.elem_offset).ravel(), eo
    )


# -- overflow fallback ------------------------------------------------------


def test_overflow_geometry_falls_back_to_unpacked():
    """A schedule whose geometry overflows 16 bits must resolve 'auto' to
    the unpacked encoding, and an explicit packed=True must raise rather
    than corrupt."""
    sched = _schedule(
        RNG.integers(0, 1000, size=128), window=64, block_rows=8
    )
    assert packable_schedule(sched)
    big = dataclasses.replace(sched, block_rows=PACK_LIMIT + 1)
    assert not packable_schedule(big)
    assert resolve_packing("auto", big) is False
    with pytest.raises(ValueError, match="packed"):
        resolve_packing(True, big)
    plan = build_device_plan(
        big, n_slices=2, cols_per_chunk=8, slice_height=8, packed="auto"
    )
    assert not plan.packed
    assert plan.meta_bytes_per_element == META_BYTES_UNPACKED
    np.testing.assert_array_equal(
        np.asarray(plan.elem_warp).ravel(), np.asarray(big.elem_warp).ravel()
    )


def test_schedule_meta_bytes_units():
    sched = _schedule(
        RNG.integers(0, 500, size=256), window=64, block_rows=8
    )
    n_elems = sched.n_windows * sched.window
    tag_bytes = sched.tags.size * 4
    assert schedule_meta_bytes(sched, packed=True) == \
        tag_bytes + n_elems * META_BYTES_PACKED
    assert schedule_meta_bytes(sched, packed=False) == \
        tag_bytes + n_elems * META_BYTES_UNPACKED


# -- kernel parity ----------------------------------------------------------


def _sell_arrays(n_slices=3, W=16, H=32, n_cols=200):
    colidx = jnp.asarray(
        RNG.integers(0, n_cols, size=(n_slices, W, H)).astype(np.int32)
    )
    values = jnp.asarray(
        (RNG.standard_normal((n_slices, W, H))
         * (RNG.random((n_slices, W, H)) < 0.7)).astype(np.float32)
    )
    return colidx, values, n_cols


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("layout", ["lane_dense", "chunk_rows"])
def test_sell_spmv_packed_depth_parity(packed, layout):
    """Both encodings on both plan layouts: lane-dense runs the x-resident
    path, chunk rows the per-warp grid."""
    colidx, values, n_cols = _sell_arrays()
    x = jnp.asarray(RNG.standard_normal(n_cols).astype(np.float32))
    sched = _schedule(np.asarray(colidx).reshape(-1), window=8 * 32,
                      block_rows=8)
    plan = build_device_plan(sched, n_slices=3, cols_per_chunk=8,
                             slice_height=32, packed=packed)
    if layout == "lane_dense":
        plan = lane_dense_plan(plan)
    y = ops.sell_spmv(
        None, values, x, cols_per_chunk=8, block_rows=8, plan=plan,
    )
    ye = ref.sell_spmv_ref(colidx, values, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ye), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("layout", ["lane_dense", "chunk_rows"])
def test_sell_spmm_packed_depth_parity(packed, layout):
    """`SpMVEngine.matmat` with either encoding, on a plan of either layout
    (32-row slices in windows of 256 stay x-resident; 16-row slices in
    windows of 64 do not)."""
    from repro.core.engine import SpMVEngine

    H, cpc = (32, 8) if layout == "lane_dense" else (16, 4)
    dense = RNG.standard_normal((96, 200)) * (RNG.random((96, 200)) < 0.1)
    sell = csr_to_sell(dense_to_csr(dense), slice_height=H)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=cpc,
                     packed=packed)
    X = jnp.asarray(RNG.standard_normal((200, 8)).astype(np.float32))
    Y = np.asarray(eng.matmat(X))
    assert eng._device_plan.lane_dense == (layout == "lane_dense")
    assert eng._device_plan.packed == packed
    np.testing.assert_allclose(Y, dense @ np.asarray(X), rtol=1e-5,
                               atol=1e-5)


# -- engine integration -----------------------------------------------------


def test_engine_cache_keys_on_packing_and_depth():
    sell = csr_to_sell(banded(256, 12, 0.7)(np.random.default_rng(0)))
    base = get_engine(sell, backend="pallas")
    assert get_engine(sell, backend="pallas") is base
    assert get_engine(sell, backend="pallas", packed=False) is not base
    # packed is keyed on the *requested* spelling (resolving would need the
    # schedule), so "auto" and True are distinct entries by design
    assert get_engine(sell, backend="pallas", packed=True) is not base


def test_engine_packed_parity_and_report():
    sell = csr_to_sell(banded(256, 12, 0.7)(np.random.default_rng(0)))
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal(sell.n_cols)
        .astype(np.float32)
    )
    from repro.core.engine import SpMVEngine

    y_ref = np.asarray(SpMVEngine(sell, backend="reference").matvec(x))
    for packed in (True, False, "auto"):
        eng = SpMVEngine(sell, backend="pallas", packed=packed)
        y = np.asarray(eng.matvec(x))
        np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    meta = SpMVEngine(sell, backend="pallas").plan_report()["metadata"]
    assert meta["packable"] and meta["packed"]
    assert meta["meta_bytes_per_element"] == META_BYTES_PACKED
    assert meta["meta_bytes_packed"] < meta["meta_bytes_unpacked"]
    assert 1.0 < meta["traffic_reduction"] <= 2.0
    # packing strictly shrinks off-chip traffic against the same ideal;
    # mem_util (achieved bandwidth) may go *down* when compute-bound —
    # fewer bytes in the same cycles — so it is reported, not ordered
    assert meta["traffic_ratio_packed"] < meta["traffic_ratio_unpacked"]
    assert meta["mem_util_packed"] > 0 and meta["mem_util_unpacked"] > 0
