"""Pure-jnp oracles for every Pallas kernel in this package.

Each oracle defines the exact semantics a kernel must reproduce; the tests
sweep shapes/dtypes and assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import jax.numpy as jnp


def coalesced_gather_ref(table: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Gather rows: (R, D) x (n,) -> (n, D)."""
    return table[indices]


def sell_spmv_ref(
    colidx: jnp.ndarray,  # (n_slices, W, H) int32
    values: jnp.ndarray,  # (n_slices, W, H)
    x: jnp.ndarray,  # (n_cols,)
) -> jnp.ndarray:
    """Padded SELL SpMV: y[s, h] = sum_w values[s, w, h] * x[colidx[s, w, h]].
    Returns (n_slices * H,)."""
    y = jnp.sum(values * x[colidx], axis=1)  # (n_slices, H)
    return y.reshape(-1)

