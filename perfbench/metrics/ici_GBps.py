"""ici_GBps: ring-accounted wire bytes of every collective call in the
window, summed over the devices, over the window's wall time."""


def read(ctx):
    if "ici" not in ctx.window.nbytes:
        return None
    return ctx.window.nbytes["ici"] / ctx.window.seconds / 1e9
