"""Reduction of a profiler trace of the measured window to device numbers.

The run wraps its window in a host span ``bench.window`` and each timed
operation in ``bench.call`` (`jax.profiler.TraceAnnotation`); everything
else on the host between calls falls under ``bench.window`` alone. From
the trace this module takes:

- busy seconds: the union of the intervals of the events on the device
  plane's "XLA Ops" line, clipped to the window (every cell runs on one
  chip; a trace with more than one busy device is refused);
- the device operations with the most time in the window, by HLO name
  (``while.3``, ``sell_spmv_pallas.1``), counting only operations that
  hold no other on their line, so a loop is not counted beside its body;
- the idle gaps in the window (its length less the union), each put to
  the innermost ``bench.`` span that holds the gap's midpoint, summed by
  that span's name.

Times are seconds. The trace is read with `jax.profiler.ProfileData`.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]  # (start, end) in ns


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    window_s: float
    busy_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a `jax.profiler.start_trace(trace_dir)`
    session wrote."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}")
    return found[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of `window` that `busy` (a sorted union) leaves free."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def _clip(iv: Interval, window: Interval) -> Optional[Interval]:
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def op_name(event_name: str) -> str:
    """``%while.3 = (f32[...]) while(...)`` -> ``while.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaves(events):
    """The events that hold no other event of the same line."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    holds, open_ = set(), []
    for k, (_, s, e) in enumerate(events):
        while open_ and events[open_[-1]][2] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][2]:
            holds.add(open_[-1])
        open_.append(k)
    return [ev for k, ev in enumerate(events) if k not in holds]


def summarize(profile, top: int = 10) -> Optional[TraceSummary]:
    """Reduce a `ProfileData` to a `TraceSummary`, or None where the trace
    holds no ``bench.window`` span or no device operation inside it."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line)]
            devices.append(ops)
        else:
            spans += [ev for line in plane.lines for ev in _events(line)
                      if ev[0].startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        return None
    window = windows[0]
    spans = [sp for sp in spans if _clip(sp[1:], window)]
    busy_devices = []
    for ops in devices:
        clipped = [(name, c) for name, s, e in ops
                   if (c := _clip((s, e), window))]
        if clipped:
            busy_devices.append(clipped)
    if not busy_devices:
        return None
    if len(busy_devices) > 1:
        raise ValueError(f"{len(busy_devices)} devices busy in the window; "
                         "the reduction reads one chip")
    clipped = busy_devices[0]
    op_ns, gap_ns = defaultdict(float), defaultdict(float)
    for name, s, e in leaves((name, s, e) for name, (s, e) in clipped):
        op_ns[op_name(name)] += e - s
    busy = union(c for _, c in clipped)
    for s, e in gaps(busy, window):
        gap_ns[_label((s + e) / 2, spans)] += e - s

    def ranked(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return TraceSummary(
        window_s=(window[1] - window[0]) * 1e-9,
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        device_ops=ranked(op_ns),
        idle_gaps=ranked(gap_ns),
    )


def _label(t: float, spans) -> str:
    """Name of the shortest benchmark span that holds time `t`."""
    holding = [(e - s, name) for name, s, e in spans if s <= t <= e]
    return min(holding)[1] if holding else "outside"
