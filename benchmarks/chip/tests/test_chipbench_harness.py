"""The harness end to end on the CPU at tiny sizes: correct runs, the
faults and the control the comparison must catch, discovery by file name,
and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench_tiny import CELLS, make_root, run

CHECK = {"hpcg8.cg8": "cg", "hpcg8.spmv": "matvec", "hpcg8.spmm8": "matmat"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))


@pytest.mark.parametrize("cell", [c for c, _, _ in CELLS])
def test_cell_runs_correct(root, cell):
    r = run(root, cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"gflop_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["metrics"]["gflop_s"]["unit"] == "GFLOP/s"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r)[-1] == "checks"
    check = r["checks"][CHECK[cell]]
    assert 0 <= check["value"] <= check["limit"]


def test_traced_run_reports_per_layer_metrics(root):
    r = run(root, "hpcg8.cg8", trace=True)
    assert r["correct"] is True
    # The CPU trace has no device plane: the trace readers find nothing
    # and their metrics are left out, never reported as 0.
    assert set(r["metrics"]) == {"plan_s", "compile_s"}
    assert "breakdown" not in r


def _altered_answer(monkeypatch):
    from repro.core.engine import SpMVEngine

    matvec = SpMVEngine.matvec

    def altered(self, x):
        y = matvec(self, x)
        return y.at[0].add(1e-2 * jnp.max(jnp.abs(y)))

    monkeypatch.setattr(SpMVEngine, "matvec", altered)


def _state_unchanged(monkeypatch):
    from repro.core import solvers

    cg = solvers.cg

    def stuck(A, b, **kw):
        res = cg(A, b, **kw)
        res.x = jnp.zeros_like(res.x)  # the iterate never leaves x0
        return res

    monkeypatch.setattr(solvers, "cg", stuck)


def _half_batch(monkeypatch):
    from repro.core.engine import SpMVEngine

    matmat = SpMVEngine.matmat

    def half(self, X):
        Y = matmat(self, X[:, : X.shape[1] // 2])
        return jnp.concatenate([Y, Y], axis=1)

    monkeypatch.setattr(SpMVEngine, "matmat", half)


@pytest.mark.parametrize("cell, fault", [
    ("hpcg8.spmv", _altered_answer),
    ("hpcg8.cg8", _state_unchanged),
    ("hpcg8.spmm8", _half_batch),
], ids=["answer_altered", "state_unchanged", "half_batch"])
def test_fault_makes_run_incorrect(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = run(root, cell)
    check = r["checks"][CHECK[cell]]
    assert r["correct"] is False and r["failed"] >= 1
    assert not check["value"] <= check["limit"]


@pytest.mark.parametrize("cell", [c for c, _, _ in CELLS])
def test_control_fails_and_program_passes(root, cell):
    """The bfloat16 control in the program's place fails the limit the
    float32 program meets."""
    from benchmarks.chip import harness

    bench = root / "benchmarks" / "chip"
    spec = harness.load_json(root / "BENCHMARK.json")
    w, config = harness.find_cell(spec, cell)
    traffic = harness.load_json(bench / "traffic" / f"{w['traffic']}.json")
    limit = traffic["limits"][CHECK[cell]]
    for seed in (1, 2, 3):
        matrix = harness.build_matrix(root, bench, config, seed)
        op = harness.make_op(bench, traffic, harness.plan_engine(matrix),
                             matrix, seed)
        program = op.compare(np.asarray(op.call(0)), 0)[CHECK[cell]]
        control = op.compare(op.expected(0, "bfloat16"), 0)[CHECK[cell]]
        assert program <= limit < control, (seed, program, limit, control)
    harness.free_program_state()


def test_new_cell_config_and_metric_are_files_only(root, tmp_path):
    """A configuration, a traffic mix and a metric join by adding files and
    naming them in BENCHMARK.json; no code of the harness changes."""
    import shutil

    new = tmp_path / "root"
    shutil.copytree(root, new)
    bench = new / "benchmarks" / "chip"
    (bench / "configs" / "hpcg5.json").write_text(json.dumps(
        {"generator": "hpcg_stencil", "params": {"nx": 5, "ny": 5, "nz": 5}}))
    (bench / "traffic" / "spmm2.json").write_text(json.dumps(
        {"op": "spmm", "k": 2, "inputs": 3, "limits": {"matmat": 1e-4}}))
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return run.calls / run.window_s\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "hpcg5", "source": "test", "why": "t",
                            "reduced": [],
                            "file": "benchmarks/chip/configs/hpcg5.json"})
    spec["workloads"].append({"name": "hpcg5.spmm2", "config": "hpcg5",
                              "traffic": "spmm2", "chips": 1, "why": "t"})
    spec["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["hpcg5.spmm2"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    r = run(new, "hpcg5.spmm2")
    assert r["correct"] is True
    assert set(r["metrics"]) == {"gflop_s", "setup_s", "calls_per_s"}
    assert r["metrics"]["calls_per_s"]["value"] > 0
    assert "calls_per_s" not in run(new, "hpcg8.spmv")["metrics"]


def test_run_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(chipbench_tiny.BENCH / "run.py"), "--workload",
         "hpcg104.cg", "--seed", "3141592653", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=chipbench_tiny.REPO)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
