"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at the geometries of the deployments they serve. Nothing runs:
these catch what interpret mode cannot (block-shape tiling rules, Mosaic
layout limits, SMEM and VMEM budgets) at no chip time.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU compiler library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coalesced_gather import coalesced_gather_pallas
from repro.kernels import sell_spmv
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
          slice_height=H, block_rows=8, lane_dense=False):
    window = cols_per_chunk * slice_height
    meta = ((n_slices * n_chunks * window // 128, 128) if lane_dense
            else (n_slices, n_chunks, 1, window))
    return DevicePlan(
        tags=_shape(sharding, (n_slices * n_chunks, max_warps), jnp.int32),
        elem_meta=_shape(sharding, meta, jnp.int32),
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
# Coalesced at a whole lane row, as `SpMVEngine` plans both where x stays in
# VMEM: HPCG's windows hold 7 warps instead of 26, webbase-1M's scattered
# windows as many as at 8.
HPCG_LANE_ROW = (35152, 4, 7)
WEBBASE_LANE_ROW = WEBBASE
# Few enough slices for one kernel call (no slice-group loop around it).
ONE_GROUP = (64, 4, 26)


def _lower_spmv(sharding, geometry, block_rows=8):
    """The engine's matvec at `geometry`: the stream held lane-dense, as
    `SpMVEngine` holds it for the x-resident path, reshaped to the kernel's
    (n_slices, W, H) signature inside the call."""
    n_slices, n_chunks, max_warps = geometry
    plan = _plan(sharding, *geometry, block_rows=block_rows, lane_dense=True)
    fn = jax.jit(lambda v, x, p: sell_spmv_pallas(
        None, v.reshape(n_slices, n_chunks * CPC, H), x, cols_per_chunk=CPC,
        block_rows=block_rows, plan=p
    ))
    return fn.lower(
        _shape(sharding, (n_slices * n_chunks * WINDOW // 128, 128),
               jnp.float32),
        _shape(sharding, (n_slices * H,), jnp.float32),
        plan,
    )


def _lower_spmv_per_warp(sharding, geometry):
    """The per-warp grid at `geometry`: a plan of chunk rows, as `SpMVEngine`
    holds it where x is over the resident budget."""
    n_slices, n_chunks, max_warps = geometry
    plan = _plan(sharding, *geometry)
    fn = jax.jit(lambda v, x, p: sell_spmv_pallas(
        None, v, x, cols_per_chunk=CPC, plan=p
    ))
    return fn.lower(
        _shape(sharding, (n_slices, n_chunks * CPC, H), jnp.float32),
        _shape(sharding, (n_slices * H,), jnp.float32),
        plan,
    )


def _lower_matmat(sharding, geometry=WEBBASE, block_rows=8, *, k=8,
                  lane_dense=True):
    """`SpMVEngine.matmat` at `geometry`: the matvec batched over k
    right-hand sides, on a lane-dense plan (the resident kernel under its
    `sequential_vmap`) or on chunk rows (the per-warp grid)."""
    n_slices, n_chunks, max_warps = geometry
    plan = _plan(sharding, *geometry, block_rows=block_rows,
                 lane_dense=lane_dense)
    values = ((n_slices * n_chunks * WINDOW // 128, 128) if lane_dense
              else (n_slices, n_chunks * CPC, H))
    fn = jax.jit(jax.vmap(lambda v, x, p: sell_spmv_pallas(
        None, v.reshape(n_slices, n_chunks * CPC, H), x, cols_per_chunk=CPC,
        block_rows=block_rows, plan=p
    ), in_axes=(None, 1, None), out_axes=1))
    return fn.lower(
        _shape(sharding, values, jnp.float32),
        _shape(sharding, (n_slices * H, k), jnp.float32),
        plan,
    )


def _lower_gather(sharding):
    """Paged-KV gather at TinyLlama-1.1B widths: 4 KV heads x 64 dims,
    16-token pages (one page per block row), bf16, 8 sequences x 2048
    tokens -> 1024 pages, one page-table window of 256 per 4096 tokens."""
    n_pages, page_width, n_windows = 1024, 16 * 4 * 64, 4
    plan = _plan(sharding, n_windows, 1, 256, cols_per_chunk=1,
                 slice_height=256, block_rows=1)
    fn = jax.jit(lambda t, p: coalesced_gather_pallas(
        t, None, window=256, block_rows=1, plan=p
    ))
    return fn.lower(
        _shape(sharding, (n_pages, page_width), jnp.bfloat16), plan,
    )


@pytest.mark.parametrize("geometry", [HPCG, WEBBASE], ids=["hpcg", "webbase"])
def test_sell_spmv_compiles_for_v5e(one_chip, geometry):
    _assert_kernel(_lower_spmv(one_chip, geometry))


@pytest.mark.parametrize("geometry", [HPCG, WEBBASE], ids=["hpcg", "webbase"])
def test_sell_spmv_per_warp_grid_compiles_for_v5e(one_chip, geometry):
    """A plan of chunk rows: the per-warp grid, slice groups and all."""
    _assert_kernel(_lower_spmv_per_warp(one_chip, geometry))


def test_sell_spmv_resident_compiles_under_vmap_for_v5e(one_chip):
    """`SpMVEngine.matmat` on an x-resident plan: the resident kernel
    batched over 8 right-hand sides, at the webbase geometry, whose 31,250
    slices leave a partial last tile."""
    _assert_kernel(_lower_matmat(one_chip))


@pytest.mark.parametrize("geometry", [HPCG, WEBBASE], ids=["hpcg", "webbase"])
def test_sell_spmv_per_warp_grid_compiles_under_vmap_for_v5e(one_chip,
                                                             geometry):
    """`SpMVEngine.matmat` on a plan of chunk rows: the per-warp grid, slice
    groups and all, batched over 8 right-hand sides."""
    _assert_kernel(_lower_matmat(one_chip, geometry, lane_dense=False))


def _elements(shape: str) -> int:
    dims = re.search(r"\[([\d,]*)\]", shape).group(1)
    return math.prod(int(d) for d in dims.split(",") if d)


@pytest.mark.parametrize("geometry", [HPCG, WEBBASE], ids=["hpcg", "webbase"])
def test_sell_spmv_is_one_resident_kernel_call(one_chip, geometry):
    """At full size the product is one `sell_spmv` kernel over
    ceil(n_slices / 8) grid steps: no loop over slice groups around it, no
    relayout of the stream, and the kernel's VMEM (x and the pipelined
    blocks) within the limit it sets."""
    n_slices, n_chunks, _ = geometry
    text = _lower_spmv(one_chip, geometry).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert " while(" not in text
    # No instruction but the parameters holds as many elements as the
    # lane-dense stream: the kernel reads values and metadata in place.
    stream = n_slices * n_chunks * WINDOW
    produced = re.findall(r"= (\w+\[[\d,]*\])\S* (?!parameter)(\w[\w-]*)\(",
                          text)
    assert produced
    assert all(_elements(shape) < stream for shape, _ in produced), produced
    limit = int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          calls[0]).group(1))
    used = int(re.search(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
        calls[0]).group(1))
    x_bytes = -(-n_slices * H // 128) * 128 * 4
    assert x_bytes <= used <= limit
    assert limit <= sell_spmv.X_RESIDENT_BUDGET + 16 * 2 ** 20


@pytest.mark.parametrize("kernel", ["matvec", "matmat_k8"])
@pytest.mark.parametrize("geometry", [HPCG_LANE_ROW, WEBBASE_LANE_ROW],
                         ids=["hpcg", "webbase"])
def test_lane_row_plan_compiles_for_v5e(one_chip, geometry, kernel):
    """A plan at `block_rows` 128: the resident matvec, and the matmat on
    the same plan, the resident kernel once per right-hand side."""
    lower = {"matvec": _lower_spmv, "matmat_k8": _lower_matmat}[kernel]
    _assert_kernel(lower(one_chip, geometry, block_rows=128))


def test_coalesced_gather_compiles_for_v5e_paged_kv(one_chip):
    _assert_kernel(_lower_gather(one_chip))


KERNELS = {
    "sell_spmv_hpcg": ("sell_spmv", lambda sh: _lower_spmv(sh, HPCG)),
    "sell_spmv_one_group": ("sell_spmv",
                            lambda sh: _lower_spmv(sh, ONE_GROUP)),
    "sell_spmv_per_warp_hpcg": ("sell_spmv",
                                lambda sh: _lower_spmv_per_warp(sh, HPCG)),
    "sell_spmv_per_warp_one_group": (
        "sell_spmv", lambda sh: _lower_spmv_per_warp(sh, ONE_GROUP)),
    "sell_spmv_matmat": ("sell_spmv", _lower_matmat),
    "sell_spmv_per_warp_matmat": (
        "sell_spmv",
        lambda sh: _lower_matmat(sh, HPCG, lane_dense=False)),
    "coalesced_gather": ("coalesced_gather", _lower_gather),
}


@pytest.mark.parametrize("case", sorted(KERNELS))
def test_kernel_is_named_after_its_scope(one_chip, case):
    """A device trace names an operation after its HLO instruction, and the
    kernel's innermost `named_scope` names that instruction: ``sell_spmv.N``
    on the x-resident path and on the per-warp grid, whether or not a
    slice-group loop or a batch of right-hand sides surrounds the call."""
    scope, lower = KERNELS[case]
    text = lower(one_chip).compile().as_text()
    names = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert names, "no kernel in the compiled program"
    assert all(re.fullmatch(rf"{scope}\.\d+", n) for n in names), names
