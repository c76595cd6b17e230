"""Named spans at the program's layer boundaries.

``span(name, **counts)`` marks one stretch of host work. It always enters
`jax.profiler.TraceAnnotation(name)`, so a profiler trace holds the span on
the same timeline as the device's operations. Inside ``recording()`` it also
appends a `Span` to the list that `recording` yields: set-up runs before a
profiler starts, and this record is what covers it. Outside a recording a
span costs the annotation alone.

Names start with their layer: ``solver.cg`` (and ``.start``, ``.loop``,
``.result`` inside it), ``engine.matvec``, ``planner`` with its stages
``planner.convert``, ``planner.digest``, ``planner.schedule`` and
``planner.lower``. Counts are whole numbers the span knows, such as the
``grid_steps`` of ``planner.lower``; the body may add to the dict the span
yields.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    parent: Optional[str]  # the innermost span open around it on its thread
    start_ns: int  # time.perf_counter_ns()
    end_ns: int
    counts: Dict[str, int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_record: Optional[List[Span]] = None
_record_lock = threading.Lock()
_local = threading.local()


def _open_names() -> List[str]:
    if not hasattr(_local, "names"):
        _local.names = []
    return _local.names


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[Dict[str, int]]:
    """Mark the block as `name`; yields `counts`, to which the body may
    add."""
    with jax.profiler.TraceAnnotation(name):
        record = _record
        if record is None:
            yield counts
            return
        names = _open_names()
        parent = names[-1] if names else None
        names.append(name)
        start = time.perf_counter_ns()
        try:
            yield counts
        finally:
            end = time.perf_counter_ns()
            names.pop()
            with _record_lock:
                if _record is record:
                    record.append(Span(name, parent, start, end, counts))


@contextlib.contextmanager
def recording() -> Iterator[List[Span]]:
    """Record every span that ends inside the block, from any thread, in the
    order they end; yields the record. Recordings do not nest."""
    global _record
    with _record_lock:
        if _record is not None:
            raise RuntimeError("spans are already being recorded")
        _record = record = []
    try:
        yield record
    finally:
        with _record_lock:
            _record = None
