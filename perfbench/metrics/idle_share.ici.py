"""idle_share.ici: the share of the traced window in which no operation
ran on the devices, averaged over them (collective cells)."""

from perfbench import trace


def read(ctx):
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.window.seconds)
