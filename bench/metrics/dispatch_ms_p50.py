"""Executor: median measured service time of one group dispatch (ms), the
scheduler's host clock around the batched call and ``block_until_ready``."""
import numpy as np


def read(ctx):
    if not ctx.dispatches:
        return None
    return float(np.median([d.service_s for d in ctx.dispatches])) * 1e3
