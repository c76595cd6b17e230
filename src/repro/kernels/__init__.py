"""Pallas TPU kernels for the paper's compute hot-spots (validated with
interpret=True on CPU): block-coalesced gather and SELL SpMV."""

from .coalesced_gather import coalesced_gather_pallas  # noqa: F401
from .sell_spmv import (  # noqa: F401
    DevicePlan,
    build_device_plan,
    sell_spmv_pallas,
)
