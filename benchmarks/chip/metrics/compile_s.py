"""Seconds of set-up that JAX spent tracing, lowering and compiling or
loading from the compile cache (`jax.monitoring` duration events)."""


def read(run):
    return run.compile_s
