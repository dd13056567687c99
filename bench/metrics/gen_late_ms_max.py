"""Load generator: how late the arrival thread released its latest query
against the schedule (ms).  A starved generator would read as a fast
server; this shows it."""
import numpy as np


def read(ctx):
    return float(np.max(ctx.late_s)) * 1e3 if len(ctx.late_s) else None
