"""mem_GBps: compulsory HBM bytes of every call completed in the window,
over the window's wall time (STREAM's convention: each array read once
and written once per sweep, counted from the shapes alone)."""


def read(ctx):
    if "hbm" not in ctx.window.nbytes:
        return None
    return ctx.window.nbytes["hbm"] / ctx.window.seconds / 1e9
