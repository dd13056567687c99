"""Executor, typed extents: real queries dispatched on typed arrival
extents (each hop reads only the traversal edges that arrive at its next
vertex type) over all real queries of the window's dispatches (%).  Reads
the scheduler's ``GroupDispatch.extents``; none from a scheduler that does
not record it."""


def read(ctx):
    ds = [d for f in ctx.flushes for d in f.dispatches]
    if not ds or not all(hasattr(d, "extents") for d in ds):
        return None
    n = sum(d.n_real for d in ds)
    if not n:
        return None
    return 100.0 * sum(d.n_real for d in ds if d.extents) / n
