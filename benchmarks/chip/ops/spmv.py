"""Back-to-back products y = A @ x through `SpMVEngine.matvec`.

Traffic keys: ``inputs`` (distinct x drawn from the seed, used in turn).
Each call ends in `block_until_ready`. Compared: ``matvec``, the largest
entrywise error against scipy in float64, scaled by |A| |x|.
"""
from __future__ import annotations

from benchmarks.chip.ops import spmm


class Op(spmm.Op):
    checks = ("matvec",)

    def __init__(self, engine, matrix, traffic: dict, seed: int):
        super().__init__(engine, matrix, {**traffic, "k": 1}, seed)

    def _shape(self):
        return (self.matrix.n_cols,)

    def _apply(self, x):
        return self.engine.matvec(x)
