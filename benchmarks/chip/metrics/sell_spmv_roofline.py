"""Share of the roofline of the `sell_spmv` kernel: the least time the chip
needs for the window's work, max(bytes / peak bytes/s, flops / peak
flops/s) with both counted from the matrix (`work.py`), over the device
time of the kernel's operations in the traced window, the leaves named
``sell_spmv.N``. Read in cells whose every call is one matrix-vector
product, so that the window's work is the kernel's. Nothing to read without
a trace, or where no operation carries the kernel's name."""

KERNEL_PREFIX = "sell_spmv."


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(t for name, t in run.trace.device_ops
                   if name.startswith(KERNEL_PREFIX))
    if kernel_s <= 0:
        return None
    least = max(run.work.bytes / run.peak_bytes_per_s,
                run.work.flops / run.peak_flops_per_s)
    return 100.0 * least / kernel_s
