"""The program's names on a device trace: the reader of
`sell_spmv_roofline` on synthetic traces with known answers, and a small
trace recorded on one TPU v5e chip (``testdata/record_trace.py``, four
8-iteration CG sets on the 8x8x8 HPCG stencil) in which the kernel is
``sell_spmv.N`` and the host's ``solver.`` and ``engine.`` spans lie on the
same timeline as the device's operations."""
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import tracing
from benchmarks.chip.metrics import sell_spmv_roofline
from benchmarks.chip.work import Work

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "v5e_cg_tiny_spans.xplane.pb"
PROGRAM_PREFIXES = ("solver.", "engine.", "planner.")


def _run(device_ops, work=Work(flops=2_000, bytes=8_000)):
    trace = tracing.TraceSummary(window_s=1e-5, busy_s=1e-5,
                                 device_ops=device_ops, idle_gaps=[])
    return NS(work=work, peak_bytes_per_s=1e9, peak_flops_per_s=1e9,
              trace=trace)


def test_roofline_reads_the_kernel_time_alone():
    run = _run([["sell_spmv.3", 4e-6], ["fusion.2", 3e-6],
                ["sell_spmv.1", 4e-6], ["sell_spmm.1", 1e-6]])
    # Least time max(8000 B / 1e9 B/s, 2000 / 1e9 flop/s) = 8 us over the
    # 8 us of the two sell_spmv instructions.
    assert sell_spmv_roofline.read(run) == pytest.approx(100.0)
    run.work = Work(flops=2_000, bytes=800)
    assert sell_spmv_roofline.read(run) == pytest.approx(100 * 2e-6 / 8e-6)


@pytest.mark.parametrize("device_ops", [
    [["closed_call.4", 1e-5], ["reshape.2", 1e-7]],  # a kernel with no name
    [["sell_spmv_pallas.1", 1e-5]],  # the pallas_call's own default name
    [["sell_spmv.3", 0.0]],
    [],
], ids=["closed_call", "pallas_default", "no_time", "no_ops"])
def test_roofline_reads_nothing_without_the_named_kernel(device_ops):
    assert sell_spmv_roofline.read(_run(device_ops)) is None


def test_roofline_reads_nothing_without_a_trace():
    run = _run([["sell_spmv.3", 1e-5]])
    run.trace = None
    assert sell_spmv_roofline.read(run) is None


@pytest.fixture(scope="module")
def recorded():
    """(window, program spans in it, kernel leaves in it, device op
    intervals in it, module names) of the recorded trace."""
    profile = tracing.load(str(RECORDED))
    spans, ops, modules = [], [], set()
    for plane in profile.planes:
        if plane.name.startswith(tracing.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    ops += list(tracing._events(line))
                elif line.name == "XLA Modules":
                    modules |= {ev.name.split("(")[0] for ev in line.events}
        else:
            spans += [ev for line in plane.lines
                      for ev in tracing._events(line)
                      if ev[0].startswith(("bench.",) + PROGRAM_PREFIXES)]
    (window,) = [(s, e) for name, s, e in spans
                 if name == tracing.WINDOW_SPAN]
    spans = [sp for sp in spans if tracing._clip(sp[1:], window)]
    kernels = [(tracing.op_name(name), s, e)
               for name, s, e in tracing.leaves(ops)
               if tracing._clip((s, e), window)
               and tracing.op_name(name).startswith("sell_spmv.")]
    busy = tracing.union(c for _, s, e in ops
                         if (c := tracing._clip((s, e), window)))
    return NS(window=window, spans=spans, kernels=kernels, busy=busy,
              modules=modules)


def test_recorded_kernel_is_named_sell_spmv():
    summary = tracing.summarize(tracing.load(str(RECORDED)))
    names = [name for name, _ in summary.device_ops]
    # The loop's products and the prologue's r = b - A x0 both show as the
    # kernel, under the scope's name and no other.
    kernel = [n for n in names if n.startswith("sell_spmv.")]
    assert len(kernel) >= 1, names
    assert not any(n.startswith(("closed_call", "sell_spmv_pallas"))
                   for n in names), names
    assert sum(t for n, t in summary.device_ops if n in kernel) > \
        0.5 * summary.busy_s


def test_recorded_modules_carry_the_runner_names(recorded):
    assert {"jit_cg_loop", "jit_engine_matvec"} <= recorded.modules
    assert not any(m.startswith("jit__lambda") for m in recorded.modules)


def test_recorded_solver_spans_nest_inside_calls(recorded):
    by_name = defaultdict(list)
    for name, s, e in recorded.spans:
        by_name[name].append((s, e))
    calls = by_name[tracing.CALL_SPAN]
    sets = by_name["solver.cg"]
    assert len(sets) == len(calls) == 4

    def inside(inner, outer):
        return outer[0] <= inner[0] <= inner[1] <= outer[1]

    for call, solve in zip(sorted(calls), sorted(sets)):
        assert inside(solve, call)
        phases = [next(iv for iv in by_name[f"solver.cg.{p}"]
                       if inside(iv, solve))
                  for p in ("start", "loop", "result")]
        assert phases == sorted(phases)
        (matvec,) = [iv for iv in by_name["engine.matvec"]
                     if inside(iv, solve)]
        assert inside(matvec, phases[0])


def _kernel_seconds_inside(kernels, spans):
    total = 0.0
    for _, s, e in kernels:
        for _, a, b in spans:
            if (c := tracing._clip((s, e), (a, b))):
                total += c[1] - c[0]
    return total * 1e-9


def test_recorded_solver_self_share(recorded):
    """The solver's own share of its spans: time inside ``solver.cg`` that
    the kernel does not fill (vector ops, eager ops, reads, idle)."""
    solves = [sp for sp in recorded.spans if sp[0] == "solver.cg"]
    span_s = sum(e - s for _, s, e in solves) * 1e-9
    kernel_s = _kernel_seconds_inside(recorded.kernels, solves)
    # Each set reads its result back inside its span, so every kernel
    # event of the window lies inside one.
    assert kernel_s == pytest.approx(
        sum(e - s for _, s, e in recorded.kernels) * 1e-9)
    share = 1 - kernel_s / span_s
    # At 512 rows a product takes microseconds and the solver's dispatch
    # and reads take the rest; at HPCG 104^3 the same share is 0.07 %.
    assert 0.5 < share < 1


def test_recorded_gaps_fall_to_solver_phases(recorded):
    program = [sp for sp in recorded.spans
               if sp[0].startswith(PROGRAM_PREFIXES)]
    by_label = defaultdict(float)
    for s, e in tracing.gaps(recorded.busy, recorded.window):
        by_label[tracing._label((s + e) / 2, program)] += (e - s) * 1e-9
    window_s = (recorded.window[1] - recorded.window[0]) * 1e-9
    busy_s = sum(e - s for s, e in recorded.busy) * 1e-9
    assert sum(by_label.values()) == pytest.approx(window_s - busy_s)
    assert {label for label in by_label if label.startswith("solver.cg.")}
    assert set(by_label) <= {"outside", "engine.matvec", "solver.cg",
                             "solver.cg.start", "solver.cg.loop",
                             "solver.cg.result"}
