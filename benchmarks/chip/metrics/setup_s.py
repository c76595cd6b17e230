"""Seconds from process start to the first timed call: device start-up,
matrix, planning, compiling (or loading from the compile cache), warm-up."""


def read(run):
    return run.setup_s
