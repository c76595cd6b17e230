"""Fused multi-column matmat: sell_spmm kernel vs vmapped matvec vs reference
across odd padded widths, k around the tile boundary, dtypes, the sharded
engine, and the streaming executor — plus the device-plan hoisting contract
(one plan per engine, colidx off the execution path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core.dist import ShardedSpMVEngine
from repro.core.engine import (
    SpMVEngine,
    clear_engine_cache,
    clear_schedule_cache,
    get_engine,
    resolve_matmat_mode,
)
from repro.core.formats import csr_to_sell, dense_to_csr
from repro.core.runtime import StreamingExecutor
from repro.kernels import ops, ref
from repro.kernels.sell_spmv import build_device_plan

RNG = np.random.default_rng(33)
K_TILE = 8
# k around the tile boundary: single column (clamped tile), one short of a
# tile, exactly one tile, and a padded tail tile (k % k_tile != 0).
KS = (1, K_TILE - 1, K_TILE, K_TILE + 3)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_engine_cache()
    clear_schedule_cache()
    yield


def _sell_case(n_rows, n_cols, density, slice_height, seed, force_width=None):
    """Random SELL matrix; `force_width` pins the max slice width (so tests
    can guarantee W % cols_per_chunk != 0 coverage deterministically)."""
    rng = np.random.default_rng(seed)
    if force_width is None:
        dense = rng.standard_normal((n_rows, n_cols)) * (
            rng.random((n_rows, n_cols)) < density
        )
    else:
        dense = np.zeros((n_rows, n_cols))
        for r in range(n_rows):
            k = force_width if r == 0 else int(rng.integers(1, force_width + 1))
            cols = rng.choice(n_cols, size=k, replace=False)
            dense[r, cols] = rng.standard_normal(k)
    return dense, csr_to_sell(dense_to_csr(dense), slice_height=slice_height)


# ---------------------------------------------------------------------------
# Kernel level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", KS)
def test_sell_spmm_kernel_matches_oracle(k, dtype):
    colidx = jnp.asarray(
        RNG.integers(0, 200, size=(3, 8, 16)).astype(np.int32)
    )
    values = jnp.asarray(
        (RNG.standard_normal((3, 8, 16))
         * (RNG.random((3, 8, 16)) < 0.7))
    ).astype(dtype)
    X = jnp.asarray(RNG.standard_normal((200, k))).astype(dtype)
    Y = ops.sell_spmm(colidx, values, X, cols_per_chunk=4, block_rows=8,
                      k_tile=K_TILE)
    Ye = ref.sell_spmm_ref(colidx, values, X)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2  # bf16 accumulation
    np.testing.assert_allclose(
        np.asarray(Y, np.float32), np.asarray(Ye, np.float32),
        rtol=tol, atol=tol,
    )
    # per column, the oracle is exactly the matvec oracle
    np.testing.assert_array_equal(
        np.asarray(Ye[:, 0]), np.asarray(ref.sell_spmv_ref(
            colidx, values, X[:, 0]
        ))
    )


def test_sell_spmm_accepts_prebuilt_plan_without_colidx():
    """With a prebuilt DevicePlan (or schedule) the column-index array is
    dead weight: both kernels run with colidx=None and agree with the
    colidx-planned call."""
    from repro.core.engine import cached_block_schedule

    colidx = RNG.integers(0, 150, size=(2, 8, 8)).astype(np.int32)
    values = RNG.standard_normal((2, 8, 8)).astype(np.float32)
    X = RNG.standard_normal((150, 5)).astype(np.float32)
    sched, _ = cached_block_schedule(
        colidx.reshape(-1), window=4 * 8, block_rows=8
    )
    plan = build_device_plan(sched, n_slices=2, cols_per_chunk=4,
                             slice_height=8)
    Y_full = ops.sell_spmm(
        jnp.asarray(colidx), jnp.asarray(values), jnp.asarray(X),
        cols_per_chunk=4, block_rows=8, k_tile=4,
    )
    Y_plan = ops.sell_spmm(
        None, jnp.asarray(values), jnp.asarray(X),
        cols_per_chunk=4, block_rows=8, k_tile=4, plan=plan,
    )
    np.testing.assert_array_equal(np.asarray(Y_full), np.asarray(Y_plan))
    y_plan = ops.sell_spmv(
        None, jnp.asarray(values), jnp.asarray(X[:, 0]),
        cols_per_chunk=4, block_rows=8, plan=plan,
    )
    np.testing.assert_allclose(
        np.asarray(y_plan), np.asarray(Y_full[:, 0]), rtol=1e-6, atol=1e-6
    )


def test_sell_spmm_requires_colidx_or_plan():
    values = jnp.asarray(RNG.standard_normal((2, 8, 8)).astype(np.float32))
    X = jnp.asarray(RNG.standard_normal((64, 4)).astype(np.float32))
    with pytest.raises(ValueError, match="colidx"):
        ops.sell_spmm(None, values, X, cols_per_chunk=4, block_rows=8)
    with pytest.raises(ValueError, match="colidx"):
        ops.sell_spmv(None, values, X[:, 0], cols_per_chunk=4, block_rows=8)


def test_colidx_values_geometry_mismatch_rejected():
    """The geometry of record is the values array's: a colidx that disagrees
    (e.g. unpadded indices next to width-padded values) must raise, not plan
    a schedule that indexes outside the kernel grid."""
    colidx = jnp.asarray(RNG.integers(0, 64, size=(2, 8, 8)).astype(np.int32))
    values_padded = jnp.asarray(
        RNG.standard_normal((2, 16, 8)).astype(np.float32)
    )
    x = jnp.asarray(RNG.standard_normal(64).astype(np.float32))
    with pytest.raises(ValueError, match="geometry"):
        ops.sell_spmv(colidx, values_padded, x, cols_per_chunk=8,
                      block_rows=8)
    with pytest.raises(ValueError, match="geometry"):
        ops.sell_spmm(colidx, values_padded, x[:, None], cols_per_chunk=8,
                      block_rows=8)


def test_sell_spmm_mismatched_plan_rejected():
    from repro.core.engine import cached_block_schedule

    colidx = RNG.integers(0, 100, size=(2, 8, 8)).astype(np.int32)
    values = jnp.asarray(RNG.standard_normal((2, 8, 8)).astype(np.float32))
    X = jnp.asarray(RNG.standard_normal((100, 4)).astype(np.float32))
    sched, _ = cached_block_schedule(
        colidx.reshape(-1), window=4 * 8, block_rows=8
    )
    plan = build_device_plan(sched, n_slices=2, cols_per_chunk=4,
                             slice_height=8)
    with pytest.raises(ValueError, match="block_rows"):
        ops.sell_spmm(None, values, X, cols_per_chunk=4, block_rows=4,
                      plan=plan)
    with pytest.raises(ValueError, match="cols_per_chunk"):
        ops.sell_spmm(None, values, X, cols_per_chunk=8, block_rows=8,
                      plan=plan)
    with pytest.raises(ValueError, match="window"):
        build_device_plan(sched, n_slices=2, cols_per_chunk=8, slice_height=8)


# ---------------------------------------------------------------------------
# Engine routing
# ---------------------------------------------------------------------------


def test_matmat_mode_resolution():
    assert resolve_matmat_mode("auto", "pallas") == "fused"
    assert resolve_matmat_mode("auto", "reference") == "vmapped"
    assert resolve_matmat_mode("vmapped", "pallas") == "vmapped"
    with pytest.raises(ValueError, match="fused"):
        resolve_matmat_mode("fused", "reference")
    with pytest.raises(ValueError, match="matmat_mode"):
        resolve_matmat_mode("mxu", "pallas")


def test_pallas_matmat_routes_fused_by_default():
    """Acceptance: matmat on the pallas backend routes through
    sell_spmm_pallas by default, within 1e-5 of the vmapped and reference
    paths for every k around the tile boundary."""
    _, sell = _sell_case(64, 96, 0.12, 16, seed=0)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4, k_tile=K_TILE)
    ref_eng = SpMVEngine(sell, backend="reference")
    assert eng.matmat_mode_resolved == "fused"
    assert ref_eng.matmat_mode_resolved == "vmapped"
    for k in KS:
        X = jnp.asarray(
            RNG.standard_normal((sell.n_cols, k)).astype(np.float32)
        )
        y_fused = np.asarray(eng.matmat(X))
        assert np.abs(y_fused - np.asarray(eng.matmat_vmapped(X))).max() <= 1e-5
        assert np.abs(y_fused - np.asarray(ref_eng.matmat(X))).max() <= 1e-5


def test_vmapped_mode_stays_bit_identical_per_column():
    """matmat_mode="vmapped" (and the reference backend always) keeps the
    per-column guarantee: matmat column j is bit-identical to matvec."""
    _, sell = _sell_case(40, 64, 0.15, 8, seed=5)
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 5)).astype(np.float32))
    for eng in (
        SpMVEngine(sell, backend="reference"),
        SpMVEngine(sell, backend="pallas", cols_per_chunk=4,
                   matmat_mode="vmapped"),
    ):
        Y = np.asarray(eng.matmat(X))
        for j in range(X.shape[1]):
            np.testing.assert_array_equal(
                Y[:, j], np.asarray(eng.matvec(X[:, j]))
            )


def test_device_plan_built_once_and_shared():
    """Satellite: the schedule is lowered to a device-resident plan exactly
    once per engine; matvec and the fused matmat share the object (no
    per-trace tag sanitize / reshape, no colidx on the execution path)."""
    _, sell = _sell_case(48, 64, 0.15, 8, seed=7)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    assert eng._device_plan is None  # lazy: planning hasn't happened
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    eng.matvec(x)
    plan = eng._device_plan
    assert plan is not None
    eng.matmat(jnp.asarray(
        RNG.standard_normal((sell.n_cols, 6)).astype(np.float32)
    ))
    assert eng._device_plan is plan  # same object, not rebuilt
    assert plan.n_slices == sell.n_slices
    assert plan.cols_per_chunk == 4


def test_fused_matmat_on_a_resident_plan_reads_the_one_stream():
    """A plan whose matvec runs the x-resident path holds its stream once,
    lane-dense. The fused matmat reads those operands (relaid out into the
    chunk rows it streams inside its own call) and the vmapped matmat runs
    the resident kernel per column, both with the matvec's results."""
    dense, sell = _sell_case(96, 160, 0.1, 32, seed=11)
    eng = SpMVEngine(sell, backend="pallas")
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), dense @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)
    assert eng._device_plan.lane_dense
    held = {key: ops[0] for key, ops in eng._operands.items()}
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 3)).astype(np.float32))
    for product in (eng.matmat, eng.matmat_vmapped):
        np.testing.assert_allclose(np.asarray(product(X)),
                                   dense @ np.asarray(X), rtol=1e-5,
                                   atol=1e-5)
    assert set(eng._operands) == set(held)
    assert all(eng._operands[key][0] is va for key, va in held.items())


def test_fused_matmat_k_edge_cases():
    _, sell = _sell_case(33, 80, 0.2, 8, seed=2, force_width=13)  # odd W
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4, k_tile=K_TILE)
    # k = 0: no columns, no kernel launch
    Y0 = np.asarray(eng.matmat(jnp.zeros((sell.n_cols, 0), jnp.float32)))
    assert Y0.shape == (sell.n_rows, 0)
    # k = 1 (clamped tile) equals matvec within tolerance
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(eng.matmat(x[:, None]))[:, 0], np.asarray(eng.matvec(x)),
        rtol=1e-5, atol=1e-5,
    )


def test_fused_matmat_bfloat16():
    _, sell = _sell_case(64, 96, 0.12, 16, seed=11)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4, k_tile=4)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, 7)).astype(np.float32)
    ).astype(jnp.bfloat16)
    y_fused = np.asarray(eng.matmat(X), np.float32)
    y_vmapped = np.asarray(eng.matmat_vmapped(X), np.float32)
    assert y_fused.dtype == np.float32 and y_fused.shape == (sell.n_rows, 7)
    np.testing.assert_allclose(y_fused, y_vmapped, rtol=5e-2, atol=5e-2)


@settings(max_examples=10, deadline=None)
@given(
    n_rows=st.integers(4, 80),
    n_cols=st.integers(8, 120),
    slice_height=st.sampled_from([8, 16]),
    cols_per_chunk=st.sampled_from([2, 4, 8]),
    k_tile=st.sampled_from([4, 8]),
    k_index=st.integers(0, len(KS) - 1),
    density=st.floats(0.05, 0.35),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_matmat_parity_property(
    n_rows, n_cols, slice_height, cols_per_chunk, k_tile, k_index, density,
    seed,
):
    """Property: for random shapes (odd widths included — the planner pads),
    the fused pallas matmat is within 1e-5 of both the vmapped pallas path
    and the reference backend, whose own matmat stays bit-identical per
    column to its matvec."""
    _, sell = _sell_case(n_rows, n_cols, density, slice_height, seed)
    k = KS[k_index]
    X = jnp.asarray(
        np.random.default_rng(seed + 1)
        .standard_normal((sell.n_cols, k)).astype(np.float32)
    )
    fused = SpMVEngine(sell, backend="pallas", cols_per_chunk=cols_per_chunk,
                       k_tile=k_tile)
    ref_eng = SpMVEngine(sell, backend="reference")
    y_fused = np.asarray(fused.matmat(X))
    y_ref = np.asarray(ref_eng.matmat(X))
    assert np.abs(y_fused - np.asarray(fused.matmat_vmapped(X))).max() <= 1e-5
    assert np.abs(y_fused - y_ref).max() <= 1e-5
    np.testing.assert_array_equal(
        y_ref[:, 0], np.asarray(ref_eng.matvec(X[:, 0]))
    )


# ---------------------------------------------------------------------------
# Sharded + streaming engines ride the fused path
# ---------------------------------------------------------------------------


def test_sharded_engine_routes_fused_and_matches_reference():
    _, sell = _sell_case(96, 128, 0.1, 16, seed=13)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, K_TILE + 3)).astype(np.float32)
    )
    sharded = ShardedSpMVEngine(sell, backend="pallas", n_shards=3,
                                cols_per_chunk=4, k_tile=K_TILE)
    assert all(e.matmat_mode_resolved == "fused" for e in sharded.engines)
    y_ref = np.asarray(SpMVEngine(sell, backend="reference").matmat(X))
    assert np.abs(np.asarray(sharded.matmat(X)) - y_ref).max() <= 1e-5
    # and the reference sharded engine stays bit-identical (vmapped path)
    sharded_ref = ShardedSpMVEngine(sell, backend="reference", n_shards=3)
    np.testing.assert_array_equal(np.asarray(sharded_ref.matmat(X)), y_ref)


def test_streaming_executor_micro_batches_ride_fused_kernel():
    _, sell = _sell_case(64, 96, 0.12, 16, seed=17)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, 13)).astype(np.float32)
    )
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4, k_tile=4)
    streamer = StreamingExecutor(eng, microbatch=4, depth=2)
    y_ref = np.asarray(SpMVEngine(sell, backend="reference").matmat(X))
    assert np.abs(np.asarray(streamer.matmat(X)) - y_ref).max() <= 1e-5
    rep = streamer.plan_report()
    assert rep["matmat"]["k"] == 4  # amortization evaluated per micro-batch
    assert rep["matmat"]["mode"] == "fused"


def test_get_engine_keys_on_k_tile_and_mode():
    _, sell = _sell_case(32, 32, 0.2, 8, seed=9)
    a = get_engine(sell, backend="pallas", cols_per_chunk=4)
    b = get_engine(sell, backend="pallas", cols_per_chunk=4, k_tile=16)
    c = get_engine(sell, backend="pallas", cols_per_chunk=4,
                   matmat_mode="vmapped")
    assert a is not b and a is not c
    assert get_engine(sell, backend="pallas", cols_per_chunk=4) is a
    # a vmapped pallas engine ignores k_tile, so it stays out of its key
    assert get_engine(sell, backend="pallas", cols_per_chunk=4,
                      matmat_mode="vmapped", k_tile=16) is c
    # the reference backend ignores both knobs (they only shape pallas plans)
    r = get_engine(sell, backend="reference")
    assert get_engine(sell, backend="reference", k_tile=16) is r
    assert get_engine(sell, backend="reference", matmat_mode="vmapped") is r
