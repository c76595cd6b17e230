"""Back-to-back fused products Y = A @ X through `SpMVEngine.matmat`.

Traffic keys: ``k`` (columns of X), ``inputs`` (distinct X drawn from the
seed, used in turn). Each call ends in `block_until_ready`. Compared:
``matmat``, the largest entrywise error against scipy in float64, scaled
by |A| |X| (see `reference.entrywise_error`).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import reference, work


class Op:
    checks = ("matmat",)

    def __init__(self, engine, matrix, traffic: dict, seed: int):
        self.engine, self.matrix = engine, matrix
        self.k = int(traffic["k"])
        rng = np.random.default_rng([seed, 1])
        self.host = [
            rng.standard_normal(self._shape(), dtype=np.float32)
            for _ in range(int(traffic["inputs"]))
        ]
        self.inputs = [jax.device_put(x) for x in self.host]
        self._ref = {}

    def _shape(self):
        return (self.matrix.n_cols, self.k)

    def _apply(self, x):
        return self.engine.matmat(x)

    def warm(self) -> None:
        self._apply(self.inputs[0]).block_until_ready()

    def call(self, i: int):
        x = self.inputs[i % len(self.inputs)]
        return self._apply(x).block_until_ready()

    def work(self) -> work.Work:
        return work.product(self.matrix, self.k)

    def expected(self, i: int, precision: str) -> np.ndarray:
        x = self.host[i % len(self.host)]
        return reference.Operator(self.matrix, precision) @ x

    def _truth(self, i: int):
        j = i % len(self.host)
        if j not in self._ref:
            self._ref[j] = (
                self.expected(j, "float64"),
                reference.abs_product(self.matrix, self.host[j]),
            )
        return self._ref[j]

    def compare(self, out: np.ndarray, i: int) -> dict:
        ref, scale = self._truth(i)
        return {self.checks[0]: reference.entrywise_error(out, ref, scale)}
