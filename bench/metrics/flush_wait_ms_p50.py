"""Scheduler, flush wait: the median time an answer spends in its flush
outside its own group's dispatch (ms): from the flush's first unit start to
its group's start, plus from its group's answers stored to the flush's last
answers stored.  Each dispatch counts once per real query it served.  Reads
the scheduler's ``GroupDispatch`` stamps; none without them."""
import numpy as np


def read(ctx):
    waits, weights = [], []
    for f in ctx.flushes:
        ds = f.dispatches
        if not ds or not hasattr(ds[0], "t_start"):
            continue
        first = min(d.t_start for d in ds)
        last = max(d.t_end for d in ds)
        for d in ds:
            waits.append((d.t_start - first) + (last - d.t_end))
            weights.append(d.n_real)
    if not waits:
        return None
    return float(np.median(np.repeat(waits, weights))) * 1e3
