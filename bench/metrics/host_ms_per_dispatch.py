"""Scheduler host time per dispatch (ms): the benchmark's wall clock around
``submit``, ``flush`` and storing the answers, less the dispatches' own
measured service time, over the window's dispatches."""


def read(ctx):
    n = len(ctx.dispatches)
    if not n:
        return None
    wall = sum(f.t_done - f.t_start for f in ctx.flushes)
    service = sum(d.service_s for d in ctx.dispatches)
    return (wall - service) / n * 1e3
