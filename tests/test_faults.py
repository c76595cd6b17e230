"""core.faults: deterministic fault injection + the recovery paths it gates.

Three layers of coverage:

  * the spec parser / FaultPlan mechanics (grammar, determinism, counters);
  * each fault site end-to-end against the real stack — store corruption
    heals via quarantine + rebuild, transient write errors via bounded retry,
    streaming dispatch timeouts via micro-batch retry, shard failures via
    degraded-mode reference recompute;
  * the chaos-determinism property: a recovered run is bit-identical to the
    fault-free run on the reference backend (matvec, matmat, and a solver).
"""
import errno
import os

import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.core import faults, schedule_store, solvers
from repro.core.dist import ShardedSpMVEngine
from repro.core.engine import (
    clear_engine_cache,
    clear_schedule_cache,
    get_engine,
    schedule_cache_stats,
)
from repro.core.faults import (
    FaultInjected,
    FaultPlan,
    InjectedCorruption,
    InjectedIOError,
    InjectedShardFailure,
    InjectedTimeout,
    parse_fault_spec,
)
from repro.core.matrices import banded
from repro.core.runtime import StreamingExecutor
from repro.launch.mesh import parse_mesh_spec

RNG = np.random.default_rng(11)


def _csr(n=192, half_bw=6, seed=0):
    return banded(n, half_bw, 0.8)(seed=seed)


# --------------------------------------------------------------------------
# spec parser
# --------------------------------------------------------------------------


def test_parse_defaults_and_full_grammar():
    sites = parse_fault_spec(
        "store_read:rate=0.3,seed=7; dispatch_timeout:after=5 ;"
        "shard_fail:rate=1,after=2,count=4"
    )
    assert set(sites) == {"store_read", "dispatch_timeout", "shard_fail"}
    sr = sites["store_read"]
    assert (sr.rate, sr.after, sr.count, sr.seed) == (0.3, 0, None, 7)
    # after without rate means ONE deterministic fault, not a dead site
    dt = sites["dispatch_timeout"]
    assert (dt.rate, dt.after, dt.count) == (1.0, 5, 1)
    sf = sites["shard_fail"]
    assert (sf.rate, sf.after, sf.count) == (1.0, 2, 4)


def test_parse_bare_site_fires_once():
    sites = parse_fault_spec("shard_fail")
    assert sites["shard_fail"].count == 1  # no rate given -> bounded


def test_parse_default_seed_flows_to_sites():
    sites = parse_fault_spec("store_read:rate=0.5", default_seed=42)
    assert sites["store_read"].seed == 42


@pytest.mark.parametrize(
    "bad",
    [
        "",
        " ; ; ",
        "nosuchsite:rate=1",
        "store_read:rate=2",
        "store_read:rate=-0.1",
        "store_read:rate=abc",
        "store_read:after=x",
        "store_read:frobnicate=1",
        "store_read:rate",
        "store_read:rate=1;store_read:rate=0.5",
    ],
)
def test_parse_rejects_malformed_specs(bad):
    with pytest.raises(ValueError, match="fault spec"):
        parse_fault_spec(bad)


# --------------------------------------------------------------------------
# FaultPlan mechanics
# --------------------------------------------------------------------------


def test_rate_sequence_is_deterministic_per_seed():
    def sequence(seed):
        plan = FaultPlan("store_read:rate=0.5", seed=seed)
        return [plan.fire("store_read") for _ in range(64)]

    a = sequence(3)
    # same seed -> identical firing sequence (the whole point of the harness)
    assert a == sequence(3)
    assert a != sequence(4)  # different seed, different deterministic stream
    assert 0 < sum(a) < 64  # rate actually thins the stream


def test_after_and_count_semantics():
    plan = FaultPlan("shard_fail:after=2,count=2")
    assert [plan.fire("shard_fail") for _ in range(6)] == [
        False, False, True, True, False, False
    ]
    rep = plan.report()
    assert rep["sites"]["shard_fail"] == {
        "events": 6, "injected": 2, "recovered": 0
    }
    assert (rep["injected"], rep["unrecovered"]) == (2, 2)


def test_unknown_site_never_fires():
    plan = FaultPlan("shard_fail")
    assert not plan.fire("store_read")
    plan.note_recovered("store_read")  # and recovery of one is a no-op
    assert plan.report()["recovered"] == 0


def test_note_recovered_clamps_to_injected():
    plan = FaultPlan("store_read:rate=1,count=1")
    assert plan.fire("store_read")
    # organic recoveries (a genuinely-corrupt file healed by the same path)
    # must not push `recovered` past `injected`
    plan.note_recovered("store_read", 5)
    plan.note_recovered("store_read", 5)
    rep = plan.report()
    assert rep["recovered"] == 1 and rep["unrecovered"] == 0


def test_maybe_inject_raises_typed_exceptions():
    with FaultPlan("store_write:rate=1"):
        with pytest.raises(InjectedIOError) as ei:
            faults.maybe_inject("store_write", "boom")
        assert isinstance(ei.value, OSError) and isinstance(
            ei.value, FaultInjected
        )
        assert ei.value.errno == errno.ENOSPC
        assert ei.value.site == "store_write"
        assert schedule_store.transient_io(ei.value)  # retry_io will retry it
    for spec, exc in (
        ("store_read", InjectedCorruption),
        ("dispatch_timeout", InjectedTimeout),
        ("shard_fail", InjectedShardFailure),
    ):
        with FaultPlan(spec):
            with pytest.raises(exc):
                faults.maybe_inject(spec)


def test_no_active_plan_is_a_noop(tmp_path):
    faults.maybe_inject("shard_fail")  # must not raise
    p = tmp_path / "x.bin"
    p.write_bytes(b"payload")
    assert not faults.corrupt_file(str(p))
    assert p.read_bytes() == b"payload"
    faults.note_recovered("shard_fail")  # and nothing to credit


def test_corrupt_file_splatters_head_and_counts(tmp_path):
    p = tmp_path / "sched.npz"
    p.write_bytes(b"PK\x03\x04" + b"z" * 256)
    with FaultPlan("store_read:rate=1,count=1") as plan:
        assert faults.corrupt_file(str(p))
        assert not p.read_bytes().startswith(b"PK")  # zip magic destroyed
        # a missing file consumes no event and cannot fire
        assert not faults.corrupt_file(str(tmp_path / "missing.npz"))
    rep = plan.report()["sites"]["store_read"]
    assert rep == {"events": 1, "injected": 1, "recovered": 0}


def test_env_var_installs_a_process_plan(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "shard_fail:after=0,count=1")
    plan = faults.active_plan()
    assert plan is not None and plan.spec == "shard_fail:after=0,count=1"
    assert faults.active_plan() is plan  # memoized until the spec changes
    monkeypatch.setenv(faults.ENV_VAR, "store_read:rate=1")
    assert faults.active_plan().spec == "store_read:rate=1"
    monkeypatch.delenv(faults.ENV_VAR)
    assert faults.active_plan() is None


def test_context_plan_shadows_env_plan(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "store_read:rate=1")
    with FaultPlan("shard_fail") as inner:
        assert faults.active_plan() is inner
    assert faults.active_plan().spec == "store_read:rate=1"
    monkeypatch.delenv(faults.ENV_VAR)


def test_suspended_masks_injection_but_not_recovery():
    with FaultPlan("store_read:rate=1") as plan:
        with faults.suspended():
            assert faults.active_plan() is None
            faults.maybe_inject("store_read")  # masked: no raise
        assert plan.report()["sites"]["store_read"]["events"] == 0
        assert plan.fire("store_read")
        with faults.suspended():
            # recovery accounting ignores the mask: the fault fired live
            faults.note_recovered("store_read")
    assert plan.report()["unrecovered"] == 0


# --------------------------------------------------------------------------
# store sites against the real persistence stack
# --------------------------------------------------------------------------


def test_store_read_corruption_quarantines_and_rebuilds(tmp_path):
    csr = _csr()
    d = str(tmp_path)
    X = RNG.standard_normal((csr.n_cols, 4)).astype(np.float32)

    eng = get_engine(csr, backend="reference", cache_dir=d)
    y_free = np.asarray(eng.matmat(X))
    files = [n for n in os.listdir(d) if n.endswith(".npz")]
    assert len(files) == 1  # warm disk cache, fault-free

    # emulate a cold process pointed at the (about-to-be-corrupted) cache
    clear_engine_cache()
    clear_schedule_cache()
    with FaultPlan("store_read:rate=1,count=1") as plan:
        eng2 = get_engine(csr, backend="reference", cache_dir=d)
        y_chaos = np.asarray(eng2.matmat(X))
        health = eng2.plan_report()["cache_health"]
    np.testing.assert_array_equal(y_chaos, y_free)  # bit-identical recovery
    assert health["quarantined"] == 1 and health["rebuilds"] == 1
    stats = schedule_cache_stats()
    assert stats["disk_rejects"] == 1 and stats["disk_saves"] == 1
    assert any(n.endswith(".bad") for n in os.listdir(d))  # quarantined file
    assert [n for n in os.listdir(d) if n.endswith(".npz")] == files  # rebuilt
    rep = plan.report()
    assert rep["injected"] == 1 and rep["unrecovered"] == 0

    # third process: the rebuilt file serves a clean warm start
    clear_engine_cache()
    clear_schedule_cache()
    eng3 = get_engine(csr, backend="reference", cache_dir=d)
    np.testing.assert_array_equal(np.asarray(eng3.matmat(X)), y_free)
    assert schedule_cache_stats()["disk_hits"] == 1


def test_store_write_transient_errors_retry_to_success(tmp_path):
    csr = _csr()
    d = str(tmp_path)
    with FaultPlan("store_write:rate=1,count=2") as plan:
        eng = get_engine(csr, backend="reference", cache_dir=d)
        eng.plan_report()  # forces plan + write-through save
    assert [n for n in os.listdir(d)] and all(
        not n.endswith(".tmp") for n in os.listdir(d)
    )
    stats = schedule_cache_stats()
    assert stats["retries"] == 2 and stats["save_errors"] == 0
    rep = plan.report()
    assert rep["injected"] == 2 and rep["unrecovered"] == 0


def test_store_write_exhaustion_degrades_to_memory_only(tmp_path):
    csr = _csr()
    d = str(tmp_path)
    X = RNG.standard_normal((csr.n_cols, 3)).astype(np.float32)
    with FaultPlan("store_write:rate=1"):  # unbounded: every attempt fails
        eng = get_engine(csr, backend="reference", cache_dir=d)
        y = np.asarray(eng.matmat(X))  # planning must still succeed
    assert y.shape == (csr.n_rows, 3)
    stats = schedule_cache_stats()
    assert stats["save_errors"] >= 1 and stats["disk_saves"] == 0
    # nothing stranded: no file, no temp droppings
    assert all(not n.endswith((".npz", ".tmp")) for n in os.listdir(d))


# --------------------------------------------------------------------------
# dispatch sites against the real engines
# --------------------------------------------------------------------------


def test_dispatch_timeout_heals_via_streaming_retry():
    csr = _csr()
    eng = get_engine(csr, backend="reference")
    X = RNG.standard_normal((csr.n_cols, 8)).astype(np.float32)
    y_free = np.asarray(eng.matmat(X))

    streamer = StreamingExecutor(eng, microbatch=4, depth=2, retries=2)
    with FaultPlan("dispatch_timeout:after=1,count=1") as plan:
        h = streamer.submit(X)
        outs = streamer.drain()
    assert outs.ok and not outs.failures
    np.testing.assert_array_equal(np.asarray(h.result()), y_free)
    assert streamer.stats["retries"] >= 1 and streamer.stats["failures"] == 0
    rep = plan.report()
    assert rep["injected"] == 1 and rep["unrecovered"] == 0


def test_dispatch_timeout_without_retry_budget_is_reported():
    csr = _csr()
    eng = get_engine(csr, backend="reference")
    X = RNG.standard_normal((csr.n_cols, 4)).astype(np.float32)
    streamer = StreamingExecutor(eng, microbatch=4, depth=2)  # retries=0
    with FaultPlan("dispatch_timeout:rate=1,count=1") as plan:
        streamer.submit(X)
        outs = streamer.drain()
    assert len(outs.failures) == 1
    assert isinstance(outs.failures[0].error, InjectedTimeout)
    assert plan.report()["unrecovered"] == 1  # honest: nothing healed it


def test_shard_failure_recovers_bit_identical_degraded_mode():
    csr = _csr(n=256)
    X = RNG.standard_normal((csr.n_cols, 4)).astype(np.float32)
    eng = ShardedSpMVEngine(
        csr, mesh=parse_mesh_spec("1,1"), backend="reference"
    )
    y_free = np.asarray(eng.matmat(X))
    assert eng.recovery_report()["recovered"] == 0

    with FaultPlan("shard_fail:rate=1,count=1") as plan:
        y_chaos = np.asarray(eng.matmat(X))
    np.testing.assert_array_equal(y_chaos, y_free)  # bit-identical
    rec = eng.plan_report()["recovery"]
    assert rec["recovered"] == 1 and rec["injected"] == 1
    ev = rec["events"][0]
    assert ev["mode"] == "reference-recompute" and ev["injected"]
    rep = plan.report()
    assert rep["injected"] == 1 and rep["unrecovered"] == 0


def test_real_shard_error_propagates(monkeypatch):
    """Only injected faults are recovered: a genuine shard failure (a
    compile or device error) must surface, not be recomputed on the
    reference backend behind the caller's back."""
    csr = _csr(n=256)
    X = RNG.standard_normal((csr.n_cols, 4)).astype(np.float32)
    eng = ShardedSpMVEngine(
        csr, mesh=parse_mesh_spec("1,1"), n_shards=2, backend="reference"
    )

    def broken(X):
        raise RuntimeError("device kernel failed to compile")

    monkeypatch.setattr(eng.engines[1], "matmat", broken)
    with pytest.raises(RuntimeError, match="failed to compile"):
        eng.matmat(X)
    assert eng.recovery_report()["recovered"] == 0


# --------------------------------------------------------------------------
# property: recovery is invisible in the numbers (reference backend)
# --------------------------------------------------------------------------


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_chaos_run_bit_identical_to_fault_free(seed):
    """FaultPlan(seed=s) on the reference backend: after recovery, matvec,
    matmat, and a full solver run match the fault-free run bit for bit."""
    import shutil
    import tempfile

    d = tempfile.mkdtemp(prefix=f"chaos{seed}-")
    csr = _csr(n=128, half_bw=5, seed=seed % 3)
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((csr.n_cols, 4)).astype(np.float32)
    x = rng.standard_normal(csr.n_cols).astype(np.float32)

    def run():
        clear_engine_cache()
        clear_schedule_cache()
        eng = get_engine(csr, backend="reference", cache_dir=d)
        res = solvers.power_iteration(
            csr, tol=1e-5, backend="reference", cache_dir=d
        )
        return (
            np.asarray(eng.matvec(x)),
            np.asarray(eng.matmat(X)),
            np.asarray(res.x),
            float(res.eigenvalue),
            int(res.iterations),
        )

    try:
        free = run()  # also warms the disk cache so store_read has a target
        spec = (
            f"store_read:rate=0.7,seed={seed};"
            f"store_write:rate=1,count=2,seed={seed}"
        )
        with FaultPlan(spec, seed=seed) as plan:
            chaos = run()
        for got, want in zip(chaos, free):
            np.testing.assert_array_equal(got, want)
        assert plan.report()["unrecovered"] == 0
    finally:
        shutil.rmtree(d, ignore_errors=True)
