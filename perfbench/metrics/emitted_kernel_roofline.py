"""emitted_kernel_roofline: the share of the device's HBM peak that the
traced calls reach in their own device time: their compulsory bytes
over the summed device time of the program executions in the traced
window, over ``hbm_GBps`` of peaks.json. The bytes come from the
pattern's shapes, whatever implements it."""

from perfbench import trace


def read(ctx):
    seconds = trace.run_s(ctx.trace)
    if "hbm" not in ctx.window.nbytes or seconds <= 0:
        return None
    rate = ctx.window.nbytes["hbm"] / seconds
    return 100.0 * rate / (float(ctx.peaks["hbm_GBps"]) * 1e9)
