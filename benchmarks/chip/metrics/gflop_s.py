"""Useful GFLOP/s: the work of the calls completed in the window (counted
from the matrix, `work.py`) over the window's whole length, which ends when
the call in flight at the deadline completes."""


def read(run):
    return run.work.flops / run.window_s / 1e9
