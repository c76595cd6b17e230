"""chip_smoke.py: its phases at tiny sizes on the CPU (Pallas interpret
mode), and its refusal to run anywhere but on a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.matrices import powerlaw

REPO = Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hpcg_phase_small_pallas():
    assert _smoke().hpcg_phase((8, 8, 8), backend="pallas", maxiter=40)


def test_webbase_phase_small_pallas():
    csr = powerlaw(3000, 3, 0.9)(seed=0)
    assert _smoke().webbase_phase(csr, backend="pallas")


def test_refuses_without_tpu(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert _smoke().main([]) == 1
    assert "{" not in capsys.readouterr().out


def test_refuses_backend_override(capsys, monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "reference")
    assert _smoke().main([]) == 2
    assert "{" not in capsys.readouterr().out


FOUR_SNIPPET = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.core.matrices import powerlaw
ok = smoke.four_chip_phase(powerlaw(4000, 3, 0.9)(seed=0), backend="pallas")
print("FOUR_OK" if ok else "FOUR_FAILED")
"""


@pytest.mark.slow
def test_four_chip_phase_on_four_host_devices():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", FOUR_SNIPPET, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "FOUR_OK", out.stdout
