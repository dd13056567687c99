"""Device: the share of the traced window (whole flushes, waits between
them included) in which no operation ran on the chip (%)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
