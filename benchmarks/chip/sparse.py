"""The benchmark's own sparse matrix: plain CSR arrays in numpy.

Generators under ``generators/`` return a `Matrix`; the harness hands its
arrays to the system under test and keeps them for the reference, the work
counter and the comparison. Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Matrix:
    n_rows: int
    n_cols: int
    indptr: np.ndarray  # (n_rows + 1,) int64
    indices: np.ndarray  # (nnz,) int32, sorted within each row
    data: np.ndarray  # (nnz,) float32

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def digest(self) -> str:
        """SHA-256 of the shape and all three arrays."""
        h = hashlib.sha256(repr((self.n_rows, self.n_cols)).encode())
        for a in (self.indptr, self.indices, self.data):
            a = np.ascontiguousarray(a)
            h.update(a.dtype.str.encode())
            h.update(a.tobytes())
        return h.hexdigest()


def coo_to_csr(n_rows: int, n_cols: int, rows: np.ndarray, cols: np.ndarray,
               vals: np.ndarray) -> Matrix:
    """CSR with entries sorted by (row, column); repeated coordinates are
    summed in float64 and stored once. Values are stored as float32."""
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order].astype(np.float64)
    if rows.size:
        new = np.ones(rows.size, dtype=bool)
        new[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
        if not new.all():
            group = np.cumsum(new) - 1
            summed = np.zeros(int(group[-1]) + 1, dtype=np.float64)
            np.add.at(summed, group, vals)
            rows, cols, vals = rows[new], cols[new], summed
    indptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return Matrix(
        n_rows=n_rows,
        n_cols=n_cols,
        indptr=np.cumsum(indptr),
        indices=cols.astype(np.int32),
        data=vals.astype(np.float32),
    )
