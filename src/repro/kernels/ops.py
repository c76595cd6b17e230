"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the kernels compile natively; everywhere else they run in
``interpret=True`` mode (Python-evaluated kernel bodies) so the whole library
is testable on CPU. ``backend="jnp"`` falls through to the oracle — used by
the framework when a call site is too small to justify a kernel launch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .coalesced_gather import coalesced_gather_pallas
from .sell_spmv import sell_spmv_pallas


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve the pallas `interpret` flag: an explicit argument wins, else
    "interpret everywhere but TPU" (the only platform these kernels compile
    natively for)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def coalesced_gather(
    table: jnp.ndarray,
    indices: jnp.ndarray | None = None,
    *,
    window: int = 256,
    block_rows: int = 8,
    max_warps: int | None = None,
    schedule=None,
    plan=None,
    packed: bool | str | None = None,
    n_out: int | None = None,
    backend: str = "pallas",
    interpret: bool | None = None,
) -> jnp.ndarray:
    if backend == "jnp":
        return ref.coalesced_gather_ref(table, indices)
    return coalesced_gather_pallas(
        table,
        indices,
        window=window,
        block_rows=block_rows,
        max_warps=max_warps,
        schedule=schedule,
        plan=plan,
        packed=packed,
        n_out=n_out,
        interpret=resolve_interpret(interpret),
    )


def sell_spmv(
    colidx: jnp.ndarray,
    values: jnp.ndarray,
    x: jnp.ndarray,
    *,
    cols_per_chunk: int = 8,
    block_rows: int = 8,
    max_warps: int | None = None,
    schedule=None,
    plan=None,
    packed: bool | str | None = None,
    backend: str = "pallas",
    interpret: bool | None = None,
) -> jnp.ndarray:
    if backend == "jnp":
        return ref.sell_spmv_ref(colidx, values, x)
    return sell_spmv_pallas(
        colidx,
        values,
        x,
        cols_per_chunk=cols_per_chunk,
        block_rows=block_rows,
        max_warps=max_warps,
        schedule=schedule,
        plan=plan,
        packed=packed,
        interpret=resolve_interpret(interpret),
    )

