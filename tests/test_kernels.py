"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.
Kernels run in interpret mode on CPU (TPU is the deployment target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float64])
@pytest.mark.parametrize(
    "rows,d,n,window,block_rows",
    [
        (64, 8, 50, 16, 4),
        (300, 16, 1000, 64, 8),
        (1000, 128, 513, 128, 8),
        (100, 4, 7, 8, 2),  # n < window (single padded window)
        (257, 32, 256, 32, 16),  # rows not multiple of block
    ],
)
def test_coalesced_gather_sweep(rows, d, n, window, block_rows, dtype):
    table = jnp.asarray(RNG.standard_normal((rows, d))).astype(dtype)
    idx = jnp.asarray(RNG.integers(0, rows, size=n).astype(np.int32))
    out = ops.coalesced_gather(
        table, idx, window=window, block_rows=block_rows
    )
    exp = ref.coalesced_gather_ref(table, idx)
    # one-hot extraction moves rows verbatim -> bitwise equal in any dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 300),
    rows=st.integers(8, 500),
    window=st.sampled_from([8, 32, 64]),
    block_rows=st.sampled_from([2, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_coalesced_gather_property(n, rows, window, block_rows, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((rows, 8)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, rows, size=n).astype(np.int32))
    out = ops.coalesced_gather(table, idx, window=window, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n_slices,W,H,n_cols,cpc,block_rows",
    [
        (3, 8, 32, 200, 8, 8),
        (5, 16, 32, 333, 8, 8),
        (2, 8, 8, 64, 4, 16),
        (7, 24, 32, 1000, 8, 32),
    ],
)
def test_sell_spmv_sweep(n_slices, W, H, n_cols, cpc, block_rows, dtype):
    colidx = jnp.asarray(
        RNG.integers(0, n_cols, size=(n_slices, W, H)).astype(np.int32)
    )
    values = jnp.asarray(
        (RNG.standard_normal((n_slices, W, H))
         * (RNG.random((n_slices, W, H)) < 0.7))
    ).astype(dtype)
    x = jnp.asarray(RNG.standard_normal(n_cols)).astype(dtype)
    y = ops.sell_spmv(colidx, values, x, cols_per_chunk=cpc,
                      block_rows=block_rows)
    ye = ref.sell_spmv_ref(colidx, values, x)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2  # bf16 accumulation
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ye, np.float32), rtol=tol,
        atol=tol,
    )


def test_sell_spmv_against_dense():
    """End to end: real matrix -> SELL -> kernel == dense matvec."""
    from repro.core.formats import dense_to_csr, csr_to_sell
    from repro.core.spmv import _sell_padded

    rng = np.random.default_rng(7)
    dense = rng.standard_normal((100, 120)) * (rng.random((100, 120)) < 0.1)
    sell = csr_to_sell(dense_to_csr(dense), width_multiple=8)
    ci, va, _ = _sell_padded(sell)
    x = rng.standard_normal(120)
    y = ops.sell_spmv(
        jnp.asarray(ci), jnp.asarray(va), jnp.asarray(x),
        cols_per_chunk=8, block_rows=8,
    )
    np.testing.assert_allclose(  # f32 on CPU (x64 disabled)
        np.asarray(y)[: sell.n_rows], dense @ x, rtol=1e-5, atol=1e-5
    )


def test_kernels_accept_prebuilt_schedule():
    """Passing an engine-cached BlockSchedule skips per-call planning and
    produces identical results to the self-planning path."""
    from repro.core.engine import cached_block_schedule

    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((300, 16)).astype(np.float32))
    idx = rng.integers(0, 300, size=1000).astype(np.int32)
    sched, _ = cached_block_schedule(idx, window=64, block_rows=8)
    out = ops.coalesced_gather(
        table, jnp.asarray(idx), window=64, block_rows=8, schedule=sched
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])

    colidx = rng.integers(0, 300, size=(3, 8, 32)).astype(np.int32)
    values = rng.standard_normal((3, 8, 32)).astype(np.float32)
    x = rng.standard_normal(300).astype(np.float32)
    ssched, _ = cached_block_schedule(
        colidx.reshape(-1), window=8 * 32, block_rows=8
    )
    y = ops.sell_spmv(
        jnp.asarray(colidx), jnp.asarray(values), jnp.asarray(x),
        cols_per_chunk=8, block_rows=8, schedule=ssched,
    )
    ye = ref.sell_spmv_ref(
        jnp.asarray(colidx), jnp.asarray(values), jnp.asarray(x)
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ye), rtol=1e-5, atol=1e-5
    )


def test_mismatched_prebuilt_schedule_rejected():
    """A schedule planned for different geometry or a different stream length
    must raise, not silently gather the wrong elements."""
    from repro.core.engine import cached_block_schedule

    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    idx = rng.integers(0, 64, size=256).astype(np.int32)
    sched, _ = cached_block_schedule(idx, window=32, block_rows=8)
    with pytest.raises(ValueError, match="window"):
        ops.coalesced_gather(
            table, jnp.asarray(idx), window=64, block_rows=8, schedule=sched
        )
    with pytest.raises(ValueError, match="block_rows"):
        ops.coalesced_gather(
            table, jnp.asarray(idx), window=32, block_rows=4, schedule=sched
        )
    with pytest.raises(ValueError, match="windows"):
        ops.coalesced_gather(
            table, jnp.asarray(idx[:100]), window=32, block_rows=8,
            schedule=sched,
        )


def test_resolve_interpret_follows_platform_and_argument(monkeypatch):
    """Only the explicit argument and the platform decide interpret mode:
    no environment variable can force interpretation on a TPU."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret() is False
    assert ops.resolve_interpret(True) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")  # ignored
    assert ops.resolve_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops.resolve_interpret() is True
    assert ops.resolve_interpret(False) is False


def test_max_warps_reduction_still_correct():
    """Caller-provided max_warps >= true per-window uniques is sufficient."""
    idx = jnp.asarray((np.arange(512) % 64).astype(np.int32))  # 8 blocks only
    table = jnp.asarray(RNG.standard_normal((64, 8)).astype(np.float32))
    out = ops.coalesced_gather(table, idx, window=128, block_rows=8,
                               max_warps=8)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(idx)])


@pytest.mark.parametrize("kernel", ["spmv", "matmat", "gather"])
def test_kernels_split_into_slice_groups(monkeypatch, kernel):
    """A plan whose tags exceed the SMEM budget runs as one kernel call per
    group of slices (the last group shifted back to overlap its predecessor);
    the stitched result must match the oracle exactly as a single call."""
    from repro.kernels import sell_spmv

    rng = np.random.default_rng(3)
    n_slices, W, H, n_cols, cpc = 7, 16, 8, 400, 4
    # 3 slices (6 windows of 128-lane tag rows) per call: groups 0-2, 3-5, 4-6
    monkeypatch.setattr(sell_spmv, "SMEM_TAG_BUDGET", 3 * (W // cpc) * 512)
    jax.clear_caches()
    if kernel == "gather":
        table = jnp.asarray(rng.standard_normal((n_cols, 16)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n_cols, size=7 * 32), jnp.int32)
        monkeypatch.setattr(sell_spmv, "SMEM_TAG_BUDGET", 3 * 512)
        out = ops.coalesced_gather(table, idx, window=32, block_rows=8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[idx])
        return
    colidx = jnp.asarray(rng.integers(0, n_cols, size=(n_slices, W, H)),
                         jnp.int32)
    values = jnp.asarray(rng.standard_normal((n_slices, W, H)), jnp.float32)
    if kernel == "matmat":
        X = jnp.asarray(rng.standard_normal((n_cols, 5)), jnp.float32)
        y = jax.vmap(
            lambda x: ops.sell_spmv(colidx, values, x, cols_per_chunk=cpc),
            in_axes=1, out_axes=1,
        )(X)
        ye = jnp.stack([ref.sell_spmv_ref(colidx, values, X[:, j])
                        for j in range(X.shape[1])], axis=1)
    else:
        x = jnp.asarray(rng.standard_normal(n_cols), jnp.float32)
        y = ops.sell_spmv(colidx, values, x, cols_per_chunk=cpc)
        ye = ref.sell_spmv_ref(colidx, values, x)
    assert sell_spmv.slices_per_call(n_slices, W // cpc, 1) == 3
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-5,
                               atol=1e-5)


def _pallas_grids(jaxpr):
    """The grid of every `pallas_call` in `jaxpr`, inner jaxprs included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                grids += _pallas_grids(inner)
    return grids


def _padded_sell(csr, H, cpc):
    """(colidx, values), each (n_slices, W, H), of `csr` with every slice
    padded to the widest, rounded up to whole chunks."""
    from repro.core.formats import csr_to_sell

    sell = csr_to_sell(csr, H)
    W = -(-int(sell.slice_widths.max()) // cpc) * cpc
    ci = np.zeros((sell.n_slices, W, H), np.int32)
    va = np.zeros((sell.n_slices, W, H), np.float32)
    for s in range(sell.n_slices):
        w, lo, hi = (int(sell.slice_widths[s]), int(sell.slice_ptrs[s]),
                     int(sell.slice_ptrs[s + 1]))
        ci[s, :w] = sell.colidx[lo:hi].reshape(w, H)
        va[s, :w] = sell.values[lo:hi].reshape(w, H)
    return jnp.asarray(ci), jnp.asarray(va), csr.n_cols


def _random_sell(n_slices, W, H, n_cols):
    rng = np.random.default_rng(n_slices * 1000 + n_cols)
    ci = rng.integers(0, n_cols, size=(n_slices, W, H)).astype(np.int32)
    va = (rng.standard_normal((n_slices, W, H))
          * (rng.random((n_slices, W, H)) < 0.7)).astype(np.float32)
    return jnp.asarray(ci), jnp.asarray(va), n_cols


def _stencil_sell(H, cpc):
    from repro.core.matrices import hpcg_stencil

    return _padded_sell(hpcg_stencil(5, 5, 5)(), H, cpc)


def _skewed_sell(H, cpc):
    from repro.core.matrices import powerlaw

    return _padded_sell(powerlaw(400, 12, alpha=1.1)(), H, cpc)


# (matrix, slice height, cols_per_chunk, block_rows, packed)
RESIDENT_CASES = {
    "block_rows4": (lambda H, c: _random_sell(8, 16, H, 1024), 32, 8, 4,
                    "auto"),
    "block_rows8_unpacked": (lambda H, c: _random_sell(16, 8, H, 512), 32, 8,
                             8, False),
    "block_rows16": (lambda H, c: _random_sell(8, 24, H, 2048), 32, 8, 16,
                     "auto"),
    "ragged_slices_and_cols": (lambda H, c: _random_sell(13, 16, H, 333), 32,
                               8, 8, "auto"),
    "stencil27": (_stencil_sell, 32, 8, 8, "auto"),
    "skewed": (_skewed_sell, 32, 4, 8, "auto"),
}
# Coalesced at a whole lane row (`SpMVEngine`'s default on this path).
RESIDENT_CASES.update({
    f"{name}_block_rows128{tag}": (*RESIDENT_CASES[name][:3], 128, packed)
    for name in ("stencil27", "skewed", "ragged_slices_and_cols")
    for packed, tag in (("auto", ""), (False, "_unpacked"))
})


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_sell_spmv_resident_parity(case, monkeypatch):
    """The x-resident path (one grid step per tile of slices) against the
    oracle and against the per-warp grid on the same plan, whose x is put
    over the budget."""
    from repro.kernels import sell_spmv

    build, H, cpc, block_rows, packed = RESIDENT_CASES[case]
    colidx, values, n_cols = build(H, cpc)
    n_slices = colidx.shape[0]
    x = jnp.asarray(np.random.default_rng(5).standard_normal(n_cols),
                    jnp.float32)

    def product():
        return ops.sell_spmv(colidx, values, x, cols_per_chunk=cpc,
                             block_rows=block_rows, packed=packed)

    # The budget is read while the kernel is traced.
    jax.clear_caches()
    try:
        grids = _pallas_grids(jax.make_jaxpr(product)().jaxpr)
        y = product()
        monkeypatch.setattr(sell_spmv, "X_RESIDENT_BUDGET", 0)
        jax.clear_caches()
        fallback_grids = _pallas_grids(jax.make_jaxpr(product)().jaxpr)
        y_grid = product()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert grids == [(-(-n_slices // sell_spmv.TILE_SLICES),)]
    assert len(fallback_grids[0]) == 3
    ye = ref.sell_spmv_ref(colidx, values, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_grid), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("case", ["stencil27", "skewed"])
def test_resident_lane_row_plan_is_bitwise_the_8_plan(case):
    """On the x-resident path a plan coalesced at a whole lane row gives the
    8-row plan's y bit for bit: every element gathers the same x entry and
    accumulates in the same order; the other copies add exact zeros."""
    from repro.kernels import sell_spmv

    build, H, cpc, _, _ = RESIDENT_CASES[case]
    colidx, values, n_cols = build(H, cpc)
    x = jnp.asarray(np.random.default_rng(9).standard_normal(n_cols),
                    jnp.float32)
    ys = {}
    for block_rows in (8, 128):
        def product():
            return ops.sell_spmv(colidx, values, x, cols_per_chunk=cpc,
                                 block_rows=block_rows)

        grids = _pallas_grids(jax.make_jaxpr(product)().jaxpr)
        assert grids == [(-(-colidx.shape[0] // sell_spmv.TILE_SLICES),)]
        ys[block_rows] = np.asarray(product())
    np.testing.assert_array_equal(ys[128], ys[8])
