"""Scheduler padding: real queries over padded batch rows, over the
window's dispatches (%).  Padding rows run on the device and answer
nothing."""


def read(ctx):
    rows = sum(d.n_real + d.n_pad for d in ctx.dispatches)
    if not rows:
        return None
    return 100.0 * sum(d.n_real for d in ctx.dispatches) / rows
