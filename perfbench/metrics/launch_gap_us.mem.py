"""launch_gap_us.mem: the median idle gap on the device between
consecutive program executions in the traced window, in microseconds:
what the driver's dispatch and each call's host round trip cost."""

import statistics

from perfbench import trace


def read(ctx):
    gaps = trace.launch_gaps_s(ctx.trace)
    return statistics.median(gaps) * 1e6 if gaps else None
