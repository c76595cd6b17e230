"""bf16 SELL values with f32 accumulation, gated against the f64 reference.

Seeds the ROADMAP bandwidth-roofline item: storing SELL values in bf16
halves the dominant memory stream, and these tests pin the numerics
contract before that kernel work lands. Contract: with bf16 values and an
f32 input vector the kernels accumulate in f32 (`promote_types`), so the
only error sources are (a) the one-time bf16 rounding of each stored
value (~2^-8 relative) and (b) f32 summation order. Against a true f64
dense reference that bounds the error by roughly

    |y - y64| <= (2^-8 + eps) * sum_j |a_ij x_j|

hence the documented gate below: BF16_TOL = 6e-3 relative to the row-wise
absolute sum (comfortably above observed ~2e-3, far below the 3e-2 gate
used for all-bf16 accumulation in test_kernels.py). A tighter second gate
checks the kernel against the jnp oracle running the *same* mixed-dtype
promotion, where only summation order differs: 1e-5.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import SpMVEngine
from repro.core.formats import csr_to_sell, dense_to_csr
from repro.core.spmv import _sell_padded
from repro.kernels import ops, ref

BF16_TOL = 6e-3  # relative to the per-row absolute sum (see module doc)


def _case(seed, n_rows=96, n_cols=120, density=0.15, cpc=4):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n_rows, n_cols)) * (
        rng.random((n_rows, n_cols)) < density
    )
    sell = csr_to_sell(dense_to_csr(dense), slice_height=8,
                       width_multiple=cpc)
    ci, va, _ = _sell_padded(sell)
    return dense, sell, ci, va


@pytest.mark.parametrize("seed", [0, 1])
def test_sell_spmv_bf16_values_f32_accum_vs_f64(seed):
    dense, sell, ci, va = _case(seed)
    rng = np.random.default_rng(seed + 50)
    x64 = rng.standard_normal(dense.shape[1])
    # f64 reference of the bf16-rounded matrix: isolates accumulation error
    # from the (exactly known) storage rounding
    va_bf = jnp.asarray(va).astype(jnp.bfloat16)
    y = ops.sell_spmv(
        jnp.asarray(ci), va_bf, jnp.asarray(x64.astype(np.float32)),
        cols_per_chunk=4, block_rows=8,
    )
    assert y.dtype == jnp.float32  # f32 accumulation is the contract
    y64 = dense @ x64  # true f64 matvec (numpy: jax runs f32 w/o x64)
    rowsum = np.abs(dense) @ np.abs(x64) + 1.0
    err = np.abs(np.asarray(y, np.float64)[: sell.n_rows] - y64)
    assert (err <= BF16_TOL * rowsum).all(), (err / rowsum).max()


def _bf16_engine(sell):
    """The pallas engine with its SELL values stored in bf16."""
    return SpMVEngine(sell, backend="pallas", cols_per_chunk=4,
                      value_dtype="bf16")


@pytest.mark.parametrize("k", [5, 1])
def test_sell_spmm_bf16_values_f32_accum_vs_f64(k):
    dense, sell, ci, va = _case(7)
    rng = np.random.default_rng(99)
    X64 = rng.standard_normal((dense.shape[1], k))
    Y = _bf16_engine(sell).matmat(jnp.asarray(X64.astype(np.float32)))
    assert Y.dtype == jnp.float32
    Y64 = dense @ X64
    rowsum = np.abs(dense) @ np.abs(X64) + 1.0
    err = np.abs(np.asarray(Y, np.float64)[: sell.n_rows] - Y64)
    assert (err <= BF16_TOL * rowsum).all(), (err / rowsum).max()


def test_bf16_kernel_matches_promoting_oracle_at_1e5():
    """Same mixed dtypes through the jnp oracle: only summation order
    differs, so the usual 1e-5 kernel gate applies."""
    dense, sell, ci, va = _case(3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal(dense.shape[1]).astype(np.float32))
    va_bf = jnp.asarray(va).astype(jnp.bfloat16)
    y = ops.sell_spmv(jnp.asarray(ci), va_bf, x, cols_per_chunk=4,
                      block_rows=8)
    ye = ref.sell_spmv_ref(jnp.asarray(ci), va_bf, x)
    assert y.dtype == ye.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ye), rtol=1e-5, atol=1e-5
    )
    X = jnp.asarray(
        rng.standard_normal((dense.shape[1], 6)).astype(np.float32)
    )
    Y = _bf16_engine(sell).matmat(X)
    Ye = jnp.stack([ref.sell_spmv_ref(jnp.asarray(ci), va_bf, X[:, j])
                    for j in range(X.shape[1])], axis=1)[: sell.n_rows]
    assert Y.dtype == Ye.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(Y), np.asarray(Ye), rtol=1e-5, atol=1e-5
    )


def test_spmm_k0_keeps_promoted_dtype():
    _, sell, _, _ = _case(5)
    X0 = jnp.zeros((120, 0), jnp.float32)
    Y = _bf16_engine(sell).matmat(X0)
    assert Y.shape[1] == 0 and Y.dtype == jnp.float32
