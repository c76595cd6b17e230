"""HPCG's 27-point stencil on an nx x ny x nz grid.

As in the HPCG reference (`GenerateProblem`): 26 on the diagonal, -1 for
each of the up to 26 neighbours inside the grid, so rows on the boundary
are shorter. Row and column ids run z fastest, then y, then x. The matrix
does not depend on the seed.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.sparse import Matrix


def build(params: dict, seed: int) -> Matrix:
    del seed
    nx, ny, nz = int(params["nx"]), int(params["ny"]), int(params["nz"])
    n = nx * ny * nz
    ix, iy, iz = (a.reshape(-1) for a in np.meshgrid(
        np.arange(nx), np.arange(ny), np.arange(nz), indexing="ij"))
    cols, keep, vals = [], [], []
    # (dx, dy, dz) in lexicographic order gives each row's columns in
    # increasing order, so the (n, 27) layout flattens straight into CSR.
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                jx, jy, jz = ix + dx, iy + dy, iz + dz
                keep.append((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                            & (jz >= 0) & (jz < nz))
                cols.append((jx * ny * nz + jy * nz + jz).astype(np.int32))
                vals.append(26.0 if dx == dy == dz == 0 else -1.0)
    keep = np.stack(keep, axis=1)
    cols = np.stack(cols, axis=1)[keep]
    data = np.broadcast_to(np.asarray(vals, np.float32), keep.shape)[keep]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return Matrix(n_rows=n, n_cols=n, indptr=indptr, indices=cols, data=data)
