"""Trace reduction: interval arithmetic, a synthetic trace with known
answers, and a small trace recorded on a TPU v5e chip."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import tracing
from benchmarks.chip.metrics import idle_share, op_roofline
from benchmarks.chip.work import Work

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "v5e_cg_tiny.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [
        (0, 4), (5, 9)]


def test_gaps_inside_window():
    busy = [(0, 4), (5, 9), (12, 20)]
    assert tracing.gaps(busy, (2, 15)) == [(4, 5), (9, 12)]
    assert tracing.gaps(busy, (-3, 25)) == [(-3, 0), (4, 5), (9, 12),
                                            (20, 25)]
    assert tracing.gaps([], (1, 2)) == [(1, 2)]


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1100),
        _ev("bench.call", 100, 600), _ev("bench.call", 650, 1100),
        _ev("other", 0, 2000),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("kernel", 50, 300), _ev("fusion", 250, 400),
            _ev("kernel", 700, 1000), _ev("late", 1050, 1300),
        ]),
        NS(name="XLA Modules", events=[_ev("module", 0, 2000)]),
    ])
    return NS(planes=[host, dev])


def test_summary_of_synthetic_trace():
    s = tracing.summarize(_profile())
    assert s.window_s == pytest.approx(1000e-9)
    # Busy in [100, 1100]: [100, 400] + [700, 1000] + [1050, 1100].
    assert s.busy_s == pytest.approx(650e-9)
    assert s.idle_share == pytest.approx(0.35)
    assert s.device_ops == [["kernel", pytest.approx(500e-9)],
                            ["fusion", pytest.approx(150e-9)],
                            ["late", pytest.approx(50e-9)]]
    # Gaps [400, 700) (midpoint 550 in the first call), [1000, 1050) (in
    # the second call); nothing is left outside the calls here.
    assert dict(s.idle_gaps) == {"bench.call": pytest.approx(350e-9)}


def test_no_window_or_no_device_reads_nothing():
    p = _profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    assert tracing.summarize(p) is None
    p = _profile()
    p.planes = p.planes[:1]
    assert tracing.summarize(p) is None


def test_second_busy_device_is_refused():
    p = _profile()
    p.planes.append(NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("kernel", 200, 300)])]))
    with pytest.raises(ValueError, match="one chip"):
        tracing.summarize(p)
    # A device with no operation inside the window is not busy in it.
    p.planes[-1].lines[0].events = [_ev("kernel", 1200, 1300)]
    assert tracing.summarize(p).busy_s == pytest.approx(650e-9)


def test_leaves_drop_events_that_hold_others():
    evs = [("loop", 0, 10), ("a", 1, 3), ("b", 3, 5), ("c", 12, 13),
           ("call", 5, 9), ("k", 6, 8)]
    assert [ev[0] for ev in tracing.leaves(evs)] == ["a", "b", "k", "c"]
    assert tracing.op_name("%while.3 = (f32[4]) while(%t)") == "while.3"


def test_trace_metric_readers():
    run = NS(work=Work(flops=2_000, bytes=8_000), peak_bytes_per_s=1e9,
             peak_flops_per_s=1e9, trace=tracing.summarize(_profile()))
    # Least time: max(8000 B / 1e9 B/s, 2000 / 1e9 flop/s) = 8 us.
    assert op_roofline.read(run) == pytest.approx(100 * 8e-6 / 650e-9)
    assert idle_share.read(run) == pytest.approx(35.0)
    run.trace = None
    assert op_roofline.read(run) is None and idle_share.read(run) is None


def test_recorded_v5e_trace():
    s = tracing.summarize(tracing.load(str(RECORDED)))
    assert s is not None
    assert 0 < s.busy_s <= s.window_s
    assert s.device_ops and all(0 < t <= s.busy_s for _, t in s.device_ops)
    names = [name for name, _ in s.device_ops]
    # The CG loop holds the kernel: the kernel is listed, the loop is not.
    assert any(n.startswith("sell_spmv_pallas") for n in names), names
    assert not any(n.startswith("while") or " " in n for n in names), names
    assert {name for name, _ in s.idle_gaps} <= {"bench.window",
                                                 "bench.call"}
    assert sum(t for _, t in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
