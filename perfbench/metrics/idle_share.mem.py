"""idle_share.mem: the share of the traced window in which no operation
ran on the device (memory-pattern cells)."""

from perfbench import trace


def read(ctx):
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.window.seconds)
