"""Sets of CG iterations through `core.solvers.cg` on the engine.

Traffic keys: ``iterations`` (per set), ``rhs`` (right-hand sides drawn
from the seed, used in turn). Each set starts from x0 = 0 with tolerance 0,
so it runs exactly ``iterations`` steps of the solver's `lax.while_loop`;
a set that stops early fails. b = A @ x_exact with x_exact standard normal,
formed in float64 and rounded to float32. Compared: ``cg``, the relative
2-norm distance of the set's iterate from textbook CG in float64 on the
same b after as many iterations.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import reference, work


class Op:
    checks = ("cg",)

    def __init__(self, engine, matrix, traffic: dict, seed: int):
        self.engine, self.matrix = engine, matrix
        self.iterations = int(traffic["iterations"])
        rng = np.random.default_rng([seed, 2])
        A = reference.Operator(matrix)
        self.host = [
            (A @ rng.standard_normal(matrix.n_cols)).astype(np.float32)
            for _ in range(int(traffic["rhs"]))
        ]
        self.inputs = [jax.device_put(b) for b in self.host]
        self._ref = {}

    def _solve(self, b, tol: float):
        from repro.core import solvers

        return solvers.cg(self.engine, b, tol=tol, maxiter=self.iterations,
                          loop="while")

    def warm(self) -> None:
        # tol = 1 ends the loop before its first step (||r0|| = ||b||): the
        # same compiled loop and eager ops as a timed set, without the work.
        self._solve(self.inputs[0], 1.0).x.block_until_ready()

    def call(self, i: int):
        res = self._solve(self.inputs[i % len(self.inputs)], 0.0)
        if res.iterations != self.iterations:
            raise RuntimeError(
                f"CG set stopped after {res.iterations} of "
                f"{self.iterations} iterations")
        return res.x.block_until_ready()

    def work(self) -> work.Work:
        return work.cg_set(self.matrix, self.iterations)

    def expected(self, i: int, precision: str) -> np.ndarray:
        b = self.host[i % len(self.host)]
        return reference.cg(reference.Operator(self.matrix, precision), b,
                            self.iterations)

    def compare(self, out: np.ndarray, i: int) -> dict:
        j = i % len(self.host)
        if j not in self._ref:
            self._ref[j] = self.expected(j, "float64")
        return {"cg": reference.normwise_error(out, self._ref[j])}
