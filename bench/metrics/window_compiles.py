"""Entry and compile cache: programs compiled or read from the persistent
cache inside the timed window.  Warm-up covers every shape the window
uses, so this reads 0."""


def read(ctx):
    return float(ctx.window_compiles)
