"""Plain reference: scipy CSR products and textbook CG, and the comparison.

``precision="float64"`` is the reference that decides ``correct``.
``precision="bfloat16"`` is its control: every product takes its matrix
values and its vector rounded to bfloat16 and sums in float32, and CG
keeps its vectors in float32. That is the step below the configurations'
float32 a later change would be tempted by; the comparison has to fail it.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

from benchmarks.chip.sparse import Matrix

PRECISIONS = ("float64", "bfloat16")


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float32)


class Operator:
    """y = A @ x of a `Matrix` in one precision."""

    def __init__(self, m: Matrix, precision: str = "float64"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.precision = precision
        if precision == "float64":
            data, self.dtype = m.data.astype(np.float64), np.float64
        else:
            data, self.dtype = _bf16(m.data), np.float32
        self.A = sp.csr_matrix((data, m.indices, m.indptr),
                               shape=(m.n_rows, m.n_cols))

    def __matmul__(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, self.dtype)
        if self.precision == "bfloat16":
            x = _bf16(x)
        return self.A @ x


def abs_product(m: Matrix, x: np.ndarray) -> np.ndarray:
    """|A| @ |x| in float64: the scale of each entry's rounding error."""
    A = sp.csr_matrix((np.abs(m.data.astype(np.float64)), m.indices,
                       m.indptr), shape=(m.n_rows, m.n_cols))
    return A @ np.abs(np.asarray(x, np.float64))


def cg(op: Operator, b: np.ndarray, iterations: int) -> np.ndarray:
    """`iterations` steps of unpreconditioned CG from x0 = 0."""
    b = np.asarray(b, op.dtype)
    x = np.zeros_like(b)
    r = b - op @ x
    p = r.copy()
    rr = r @ r
    for _ in range(iterations):
        Ap = op @ p
        alpha = rr / (p @ Ap)
        x += alpha * p
        r -= alpha * Ap
        rr_new = r @ r
        p = r + (rr_new / rr) * p
        rr = rr_new
    return x


def entrywise_error(y: np.ndarray, ref: np.ndarray,
                    scale: np.ndarray) -> float:
    """max |y - ref| / (|A| |x|) over entries; an entry whose scale is 0
    must be exactly 0. Any non-finite entry reads inf."""
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape or not np.all(np.isfinite(y)):
        return float("inf")
    err = np.abs(y - ref)
    live = scale > 0
    if np.any(err[~live] > 0):
        return float("inf")
    return float(np.max(err[live] / scale[live], initial=0.0))


def normwise_error(x: np.ndarray, ref: np.ndarray) -> float:
    """||x - ref||_2 / ||ref||_2; any non-finite entry reads inf."""
    x = np.asarray(x, np.float64)
    if x.shape != ref.shape or not np.all(np.isfinite(x)):
        return float("inf")
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))
