"""Multi-column matmat: the pallas engine's batched matvec vs the oracle, the
per-column matvec and the reference backend across odd padded widths, widths
of k, dtypes, both kernel paths, the sharded engine, and the streaming
executor — plus the device-plan hoisting contract (one plan per engine,
colidx off the execution path) and the plan checks of the kernel itself."""
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core.dist import ShardedSpMVEngine
from repro.core.engine import (
    SpMVEngine,
    clear_engine_cache,
    clear_schedule_cache,
)
from repro.core.formats import csr_to_sell, dense_to_csr
from repro.core.runtime import StreamingExecutor
from repro.core.spmv import _sell_padded
from repro.kernels import ops, ref
from repro.kernels.sell_spmv import build_device_plan

RNG = np.random.default_rng(33)
# Widths of k: one column, an odd width, eight, and eleven.
KS = (1, 7, 8, 11)


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


def _oracle(sell, X, value_dtype=None):
    """Column j of A @ X by the matvec oracle on the padded SELL arrays,
    values stored at `value_dtype` (default: X's dtype)."""
    ci, va, _ = _sell_padded(sell)
    va = jnp.asarray(va).astype(value_dtype or X.dtype)
    cols = [ref.sell_spmv_ref(jnp.asarray(ci), va, X[:, j])
            for j in range(X.shape[1])]
    return np.stack([np.asarray(c, np.float32) for c in cols],
                    axis=1)[: sell.n_rows]


# ---------------------------------------------------------------------------
# Against the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", KS)
def test_sell_spmm_kernel_matches_oracle(k, dtype):
    """The pallas engine's matmat against the matvec oracle per column, with
    values stored at the RHS dtype."""
    _, sell = _sell_case(48, 200, 0.3, 16, seed=3)
    eng = SpMVEngine(
        sell, backend="pallas", cols_per_chunk=4,
        value_dtype="bf16" if dtype == jnp.bfloat16 else None,
    )
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, k))).astype(dtype)
    Y = eng.matmat(X)
    assert Y.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 5e-2  # bf16 accumulation
    np.testing.assert_allclose(
        np.asarray(Y, np.float32), _oracle(sell, X), rtol=tol, atol=tol,
    )


def test_sell_spmv_accepts_prebuilt_plan_without_colidx():
    """With a prebuilt DevicePlan the column-index array is dead weight: the
    kernel runs with colidx=None and agrees with the colidx-planned call."""
    from repro.core.engine import cached_block_schedule

    colidx = RNG.integers(0, 150, size=(2, 8, 8)).astype(np.int32)
    values = RNG.standard_normal((2, 8, 8)).astype(np.float32)
    x = RNG.standard_normal(150).astype(np.float32)
    sched, _ = cached_block_schedule(
        colidx.reshape(-1), window=4 * 8, block_rows=8
    )
    plan = build_device_plan(sched, n_slices=2, cols_per_chunk=4,
                             slice_height=8)
    y_full = ops.sell_spmv(
        jnp.asarray(colidx), jnp.asarray(values), jnp.asarray(x),
        cols_per_chunk=4, block_rows=8,
    )
    y_plan = ops.sell_spmv(
        None, jnp.asarray(values), jnp.asarray(x),
        cols_per_chunk=4, block_rows=8, plan=plan,
    )
    np.testing.assert_array_equal(np.asarray(y_full), np.asarray(y_plan))


def test_sell_spmv_requires_colidx_or_plan():
    values = jnp.asarray(RNG.standard_normal((2, 8, 8)).astype(np.float32))
    x = jnp.asarray(RNG.standard_normal(64).astype(np.float32))
    with pytest.raises(ValueError, match="colidx"):
        ops.sell_spmv(None, values, x, cols_per_chunk=4, block_rows=8)


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


def test_sell_spmv_mismatched_plan_rejected():
    from repro.core.engine import cached_block_schedule

    colidx = RNG.integers(0, 100, size=(2, 8, 8)).astype(np.int32)
    values = jnp.asarray(RNG.standard_normal((2, 8, 8)).astype(np.float32))
    x = jnp.asarray(RNG.standard_normal(100).astype(np.float32))
    sched, _ = cached_block_schedule(
        colidx.reshape(-1), window=4 * 8, block_rows=8
    )
    plan = build_device_plan(sched, n_slices=2, cols_per_chunk=4,
                             slice_height=8)
    with pytest.raises(ValueError, match="block_rows"):
        ops.sell_spmv(None, values, x, cols_per_chunk=4, block_rows=4,
                      plan=plan)
    with pytest.raises(ValueError, match="cols_per_chunk"):
        ops.sell_spmv(None, values, x, cols_per_chunk=8, block_rows=8,
                      plan=plan)
    with pytest.raises(ValueError, match="window"):
        build_device_plan(sched, n_slices=2, cols_per_chunk=8, slice_height=8)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def test_vmapped_mode_stays_bit_identical_per_column():
    """On both backends matmat column j is bit-identical to matvec (here a
    pallas plan on the per-warp grid)."""
    _, sell = _sell_case(40, 64, 0.15, 8, seed=5)
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 5)).astype(np.float32))
    for eng in (
        SpMVEngine(sell, backend="reference"),
        SpMVEngine(sell, backend="pallas", cols_per_chunk=4),
    ):
        Y = np.asarray(eng.matmat(X))
        for j in range(X.shape[1]):
            np.testing.assert_array_equal(
                Y[:, j], np.asarray(eng.matvec(X[:, j]))
            )


@pytest.mark.parametrize("k", [1, 3, 8])
def test_lane_dense_matmat_is_matvec_per_column(k):
    """On an x-resident plan matmat runs the resident kernel once per column
    over the operands the matvec holds (no second copy of the stream), and
    column j is ``matvec(X[:, j])`` bit for bit."""
    dense, sell = _sell_case(96, 160, 0.1, 32, seed=11)
    eng = SpMVEngine(sell, backend="pallas")
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), dense @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)
    assert eng._device_plan.lane_dense
    held = {key: ops[0] for key, ops in eng._operands.items()}
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, k)).astype(np.float32))
    Y = np.asarray(eng.matmat(X))
    for j in range(k):
        np.testing.assert_array_equal(Y[:, j], np.asarray(eng.matvec(X[:, j])))
    assert set(eng._operands) == set(held)
    assert all(eng._operands[key][0] is va for key, va in held.items())


# Ways a plan leaves the x-resident path: (slice_height, cols_per_chunk,
# value_dtype, x over the resident budget).
FALLBACKS = {
    "bf16_values": (32, 8, "bf16", False),
    "x_over_budget": (32, 8, None, True),
    "window_not_lane_rows": (16, 4, None, False),
}


@pytest.mark.parametrize("product", ["matvec", "matmat"])
@pytest.mark.parametrize("cause", sorted(FALLBACKS))
def test_per_warp_fallback_matches_oracle(cause, product, monkeypatch):
    """Where `resident_geometry` fails the engine plans chunk rows at the
    8-row coalescing granularity, and its products run the per-warp grid
    and match the oracle."""
    from repro.kernels import sell_spmv

    H, cpc, value_dtype, over_budget = FALLBACKS[cause]
    _, sell = _sell_case(96, 160, 0.1, H, seed=19)
    if over_budget:
        monkeypatch.setattr(sell_spmv, "X_RESIDENT_BUDGET", 0)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=cpc,
                     value_dtype=value_dtype)
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 3)).astype(np.float32))
    if product == "matvec":
        Y = np.asarray(eng.matvec(X[:, 0]))[:, None]
        X = X[:, :1]
    else:
        Y = np.asarray(eng.matmat(X))
    assert not eng._device_plan.lane_dense
    assert eng.block_rows == 8
    np.testing.assert_allclose(
        Y, _oracle(sell, X, value_dtype=jnp.bfloat16 if value_dtype else None),
        rtol=1e-5, atol=1e-5,
    )


def test_device_plan_built_once_and_shared():
    """Satellite: the schedule is lowered to a device-resident plan exactly
    once per engine; matvec and matmat share the object (no per-trace tag
    sanitize / reshape, no colidx on the execution path)."""
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


def test_fused_matmat_k_edge_cases():
    _, sell = _sell_case(33, 80, 0.2, 8, seed=2, force_width=13)  # odd W
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    # k = 0: no columns, no kernel launch
    Y0 = eng.matmat(jnp.zeros((sell.n_cols, 0), jnp.float32))
    assert Y0.shape == (sell.n_rows, 0) and Y0.dtype == jnp.float32
    # k = 1 is the matvec
    x = jnp.asarray(RNG.standard_normal(sell.n_cols).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(eng.matmat(x[:, None]))[:, 0], np.asarray(eng.matvec(x)),
    )
    # odd k
    X = jnp.asarray(RNG.standard_normal((sell.n_cols, 5)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(eng.matmat(X)), _oracle(sell, X),
                               rtol=1e-5, atol=1e-5)


def test_fused_matmat_bfloat16():
    """A bf16 RHS: values cast to it, the product in bf16, against the f32
    oracle at bf16 tolerance."""
    _, sell = _sell_case(64, 96, 0.12, 16, seed=11)
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, 7)).astype(np.float32)
    ).astype(jnp.bfloat16)
    Y = np.asarray(eng.matmat(X), np.float32)
    assert Y.shape == (sell.n_rows, 7)
    np.testing.assert_allclose(
        Y, _oracle(sell, X.astype(jnp.float32)), rtol=5e-2, atol=5e-2
    )


@settings(max_examples=10, deadline=None)
@given(
    n_rows=st.integers(4, 80),
    n_cols=st.integers(8, 120),
    slice_height=st.sampled_from([8, 16, 32]),
    cols_per_chunk=st.sampled_from([2, 4, 8]),
    k_index=st.integers(0, len(KS) - 1),
    density=st.floats(0.05, 0.35),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_matmat_parity_property(
    n_rows, n_cols, slice_height, cols_per_chunk, k_index, density, seed,
):
    """Property: for random shapes (odd widths included — the planner pads),
    the pallas matmat is within 1e-5 of the reference backend and its
    column 0 is the pallas matvec bit for bit; the reference matmat stays
    bit-identical per column to its matvec."""
    _, sell = _sell_case(n_rows, n_cols, density, slice_height, seed)
    k = KS[k_index]
    X = jnp.asarray(
        np.random.default_rng(seed + 1)
        .standard_normal((sell.n_cols, k)).astype(np.float32)
    )
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=cols_per_chunk)
    ref_eng = SpMVEngine(sell, backend="reference")
    y = np.asarray(eng.matmat(X))
    y_ref = np.asarray(ref_eng.matmat(X))
    assert np.abs(y - y_ref).max() <= 1e-5
    np.testing.assert_array_equal(y[:, 0], np.asarray(eng.matvec(X[:, 0])))
    np.testing.assert_array_equal(
        y_ref[:, 0], np.asarray(ref_eng.matvec(X[:, 0]))
    )


# ---------------------------------------------------------------------------
# Sharded + streaming engines
# ---------------------------------------------------------------------------


def test_sharded_engine_routes_fused_and_matches_reference():
    _, sell = _sell_case(96, 128, 0.1, 16, seed=13)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, 11)).astype(np.float32)
    )
    sharded = ShardedSpMVEngine(sell, backend="pallas", n_shards=3,
                                cols_per_chunk=4)
    assert all(e.backend_resolved == "pallas" for e in sharded.engines)
    y_ref = np.asarray(SpMVEngine(sell, backend="reference").matmat(X))
    assert np.abs(np.asarray(sharded.matmat(X)) - y_ref).max() <= 1e-5
    # and the reference sharded engine stays bit-identical
    sharded_ref = ShardedSpMVEngine(sell, backend="reference", n_shards=3)
    np.testing.assert_array_equal(np.asarray(sharded_ref.matmat(X)), y_ref)


def test_streaming_executor_micro_batches_ride_fused_kernel():
    _, sell = _sell_case(64, 96, 0.12, 16, seed=17)
    X = jnp.asarray(
        RNG.standard_normal((sell.n_cols, 13)).astype(np.float32)
    )
    eng = SpMVEngine(sell, backend="pallas", cols_per_chunk=4)
    streamer = StreamingExecutor(eng, microbatch=4, depth=2)
    y_ref = np.asarray(SpMVEngine(sell, backend="reference").matmat(X))
    assert np.abs(np.asarray(streamer.matmat(X)) - y_ref).max() <= 1e-5
    rep = streamer.plan_report()
    assert rep["streaming"]["microbatch"] == 4
    assert "matmat" not in rep
