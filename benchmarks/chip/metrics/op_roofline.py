"""Share of the roofline: the least time the chip needs for the work the
traced window completed, max(bytes / peak bytes/s, flops / peak flops/s)
with both counted from the matrix (`work.py`), over the device's busy time
in that window. Nothing to read without a trace or a busy device."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    least = max(run.work.bytes / run.peak_bytes_per_s,
                run.work.flops / run.peak_flops_per_s)
    return 100.0 * least / run.trace.busy_s
