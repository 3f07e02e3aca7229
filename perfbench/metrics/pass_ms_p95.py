"""pass_ms_p95: the 95th percentile, over every pass of the window, of
one pass's time in ms: the interval from the previous pass's completion
to its own, as the host sees them (passes are dispatched ahead). A pass
makes the traffic's calls per rung, rung after rung, and spans 250 ms or
more of the host clock."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.window.passes, 95)) * 1e3
