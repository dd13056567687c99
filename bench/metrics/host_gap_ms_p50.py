"""Scheduler, between dispatches: the median host time from one unit's
answers ready on the device to the next unit's launch within a flush (ms),
while the chip waits.  Reads the scheduler's ``GroupDispatch`` stamps;
none without them."""
import numpy as np


def read(ctx):
    gaps = []
    for f in ctx.flushes:
        ds = f.dispatches
        if not ds or not hasattr(ds[0], "t_launch"):
            continue
        gaps += [b.t_launch - a.t_ready for a, b in zip(ds, ds[1:])]
    return float(np.median(gaps)) * 1e3 if gaps else None
