"""Share of the HBM roofline per product: the compulsory bytes of the
products in the traced window (``bench/work_spmm.py``) over peak HBM
bandwidth, over the device's busy time in that window, in percent."""


def read(ctx):
    if not ctx.completed or ctx.trace.busy_s <= 0:
        return None
    least_s = ctx.work_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace.busy_s
