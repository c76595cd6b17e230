"""The work counter and the benchmark's own matrix generators."""
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import work
from benchmarks.chip.generators import hpcg_stencil
from benchmarks.chip.sparse import Matrix, coo_to_csr

HPCG4 = {"nx": 4, "ny": 4, "nz": 4}


def five_by_five() -> Matrix:
    dense = np.array([[1, 0, 2, 0, 0], [0, 0, 0, 0, 0], [0, 3, 0, 0, 4],
                      [5, 0, 0, 0, 0], [0, 6, 7, 8, 9]], dtype=np.float64)
    rows, cols = np.nonzero(dense)
    return coo_to_csr(5, 5, rows, cols, dense[rows, cols])


def test_product_counts_by_hand():
    m = five_by_five()
    assert m.nnz == 9 and list(m.indptr) == [0, 2, 2, 4, 5, 9]
    # 2 flops per nonzero; 4 B value + 4 B index per nonzero, 6 row
    # pointers, x read and y written once.
    assert work.product(m) == work.Work(18, 9 * 8 + 6 * 4 + 5 * 4 + 5 * 4)
    assert work.product(m, 3) == work.Work(54, 72 + 24 + 3 * (20 + 20))


def test_hpcg_stencil_4_counts_by_hand():
    m = hpcg_stencil.build(HPCG4, 0)
    # Ten (position, neighbour) pairs per axis: 10^3 nonzeros in 64 rows.
    assert (m.n_rows, m.nnz) == (64, 1000)
    assert work.product(m) == work.Work(2000, 8000 + 65 * 4 + 64 * 4 * 2)
    assert work.product(m, 8) == work.Work(16000, 8260 + 64 * 4 * 8 * 2)
    # Start: dot, product, axpy, dot; each step: product, 2 dots, 3 axpys.
    start = work.Work(128 + 2000 + 128 + 128, 512 + 8772 + 768 + 512)
    step = work.Work(2000 + 256 + 384, 8772 + 1024 + 2304)
    assert work.cg_set(m, 3) == work.Work(start.flops + 3 * step.flops,
                                          start.bytes + 3 * step.bytes)


def test_duplicates_are_summed_once():
    m = coo_to_csr(2, 3, np.array([1, 0, 1]), np.array([2, 1, 2]),
                   np.array([0.5, 1.0, 0.25]))
    assert list(m.indptr) == [0, 1, 2] and list(m.indices) == [1, 2]
    assert list(m.data) == [1.0, 0.75] and m.data.dtype == np.float32


@pytest.mark.parametrize("params, seed, digest", [
    (HPCG4, 0,
     "b17a7df2bb1315285c5e4d54b48ea6d5943e76bf6eb2688a7144d4ef086bf607"),
    ({"nx": 5, "ny": 3, "nz": 7}, 0,
     "c73e7d68d01a37c9ac5d1def289c96554df73a838b3502100db75e05bd4db669"),
], ids=["4x4x4", "5x3x7"])
def test_hpcg_stencil_pinned(params, seed, digest):
    assert hpcg_stencil.build(params, seed).digest() == digest


@pytest.mark.parametrize("op_name, traffic", [
    ("spmv", {"inputs": 1}), ("spmm", {"k": 8, "inputs": 1}),
    ("cg", {"iterations": 4, "rhs": 1}),
])
def test_work_ignores_the_plan(op_name, traffic):
    """Work and bytes read the same whatever plan the program builds."""
    from repro.core.engine import clear_engine_cache, get_engine
    from repro.core.formats import CSRMatrix

    from benchmarks.chip import harness

    m = hpcg_stencil.build(HPCG4, 0)
    csr = CSRMatrix(m.n_rows, m.n_cols, m.indptr, m.indices, m.data)
    seen = set()
    for cpc, packed in [(4, True), (8, False), (8, "auto")]:
        clear_engine_cache()
        eng = get_engine(csr, backend="pallas", cols_per_chunk=cpc,
                         packed=packed)
        op = harness.make_op(chipbench_tiny.BENCH,
                             {"op": op_name, **traffic}, eng, m, 7)
        seen.add(op.work())
        readings = op.compare(np.asarray(op.call(0)), 0)
        assert all(v < 1e-5 for v in readings.values()), readings
    clear_engine_cache()
    assert len(seen) == 1
    expected = {"spmv": work.product(m), "spmm": work.product(m, 8),
                "cg": work.cg_set(m, 4)}[op_name]
    assert seen == {expected}
