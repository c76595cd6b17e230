"""Useful work of an operation, counted from the generated matrix alone.

Floating-point operations are counted as HPCG counts them: 2 per stored
nonzero and right-hand side in a product, 2n per dot product and 2n per
axpy. Bytes are the algorithm's minimum traffic in the configuration's
precision (float32 values and vectors, int32 indices and row pointers):
CSR values, column indices and row pointers read once per product, each
input vector read once and each output vector written once. Neither count
looks at how the program stores or schedules the matrix, so both read the
same for any implementation of the same operation.
"""
from __future__ import annotations

import dataclasses

from benchmarks.chip.sparse import Matrix

VALUE_BYTES = 4
INDEX_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: int
    bytes: int

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, times: int) -> "Work":
        return Work(self.flops * times, self.bytes * times)


def product(m: Matrix, k: int = 1) -> Work:
    """Y = A @ X with X of shape (n_cols, k)."""
    matrix_bytes = (m.nnz * (VALUE_BYTES + INDEX_BYTES)
                    + (m.n_rows + 1) * INDEX_BYTES)
    vector_bytes = (m.n_cols + m.n_rows) * k * VALUE_BYTES
    return Work(2 * m.nnz * k, matrix_bytes + vector_bytes)


def dot(n: int) -> Work:
    return Work(2 * n, 2 * n * VALUE_BYTES)


def axpy(n: int) -> Work:
    """w = a*x + y (also b - A x): two reads, one write."""
    return Work(2 * n, 3 * n * VALUE_BYTES)


def cg_set(m: Matrix, iterations: int) -> Work:
    """Unpreconditioned CG from x0 = 0: ||b||^2, r = b - A x0, ||r||^2,
    then per iteration one product, two dots and three axpys."""
    n = m.n_rows
    start = dot(n) + product(m) + axpy(n) + dot(n)
    step = product(m) + dot(n) * 2 + axpy(n) * 3
    return start + step * iterations
