"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at the geometries of the deployments they serve. Nothing runs:
these catch what interpret mode cannot (block-shape tiling rules, Mosaic
layout limits, SMEM and VMEM budgets) at no chip time.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coalesced_gather import coalesced_gather_pallas
from repro.kernels.sell_spmm import sell_spmm_pallas
from repro.kernels.sell_spmv import DevicePlan, sell_spmv_pallas

H = 32  # SELL slice height
CPC = 8  # cols_per_chunk; window = CPC * H
WINDOW = CPC * H


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _plan(sharding, n_slices, n_chunks, max_warps, *, cols_per_chunk=CPC,
          slice_height=H, block_rows=8):
    window = cols_per_chunk * slice_height
    return DevicePlan(
        tags=_shape(sharding, (n_slices * n_chunks, max_warps), jnp.int32),
        elem_meta=_shape(sharding, (n_slices, n_chunks, 1, window), jnp.int32),
        window=window, block_rows=block_rows, cols_per_chunk=cols_per_chunk,
        slice_height=slice_height, n_slices=n_slices, n_chunks=n_chunks,
        packed=True,
    )


def _assert_kernel(lowered):
    compiled = lowered.compile()  # raises what the chip's compiler raises
    assert "tpu_custom_call" in compiled.as_text()


# (n_slices, n_chunks, max_warps) of the paper-scale generators:
# HPCG 27-point 104^3 (1,124,864 rows) and webbase-1M (1,000,000 rows).
HPCG = (35152, 4, 26)
WEBBASE = (31250, 8, 120)


@pytest.mark.parametrize("geometry", [HPCG, WEBBASE], ids=["hpcg", "webbase"])
def test_sell_spmv_compiles_for_v5e(one_chip, geometry):
    n_slices, n_chunks, max_warps = geometry
    plan = _plan(one_chip, *geometry)
    fn = jax.jit(lambda v, x, p: sell_spmv_pallas(
        None, v, x, cols_per_chunk=CPC, plan=p
    ))
    _assert_kernel(fn.lower(
        _shape(one_chip, (n_slices, n_chunks * CPC, H), jnp.float32),
        _shape(one_chip, (n_slices * H,), jnp.float32),
        plan,
    ))


def test_sell_spmm_compiles_for_v5e_at_k8(one_chip):
    n_slices, n_chunks, max_warps = WEBBASE
    plan = _plan(one_chip, *WEBBASE)
    fn = jax.jit(lambda v, x, p: sell_spmm_pallas(
        None, v, x, cols_per_chunk=CPC, k_tile=8, plan=p
    ))
    _assert_kernel(fn.lower(
        _shape(one_chip, (n_slices, n_chunks * CPC, H), jnp.float32),
        _shape(one_chip, (n_slices * H, 8), jnp.float32),
        plan,
    ))


def test_coalesced_gather_compiles_for_v5e_paged_kv(one_chip):
    """Paged-KV gather at TinyLlama-1.1B widths: 4 KV heads x 64 dims,
    16-token pages (one page per block row), bf16, 8 sequences x 2048
    tokens -> 1024 pages, one page-table window of 256 per 4096 tokens."""
    n_pages, page_width, n_windows = 1024, 16 * 4 * 64, 4
    plan = _plan(one_chip, n_windows, 1, 256, cols_per_chunk=1,
                 slice_height=256, block_rows=1)
    fn = jax.jit(lambda t, p: coalesced_gather_pallas(
        t, None, window=256, block_rows=1, plan=p
    ))
    _assert_kernel(fn.lower(
        _shape(one_chip, (n_pages, page_width), jnp.bfloat16), plan,
    ))
