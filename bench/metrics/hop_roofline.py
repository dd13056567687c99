"""Kernel, hop delivery: the least time the traced dispatches' hops need at
the chip's peaks (``work.hop_work`` over the real queries: padding is
waste), over the device's busy time in the traced window (%)."""


def read(ctx):
    if ctx.trace is None or ctx.least_time_s is None or not ctx.traced:
        return None
    if ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * ctx.least_time_s / ctx.trace["busy_s"]
