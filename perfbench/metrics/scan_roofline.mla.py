"""scan_roofline.mla: the share of the HBM peak that the MLA decode
step's scan over the latent cache reaches in its own device time: the
cache bytes every traced call must read (``cache_read`` of the config's
``traffic_bytes``: every layer's cache, all T rows of c + r bf16 values)
over the device time of the ops in the scope ``repro.mla.scan``, over
``hbm_GBps`` of peaks.json. The device time of every scope, unscoped
ops included, goes to standard error."""

import sys

from perfbench import scopes


def read(ctx):
    sec = scopes.seconds(ctx.trace)
    if sec:
        print("device scopes: " + ", ".join(f"{k} {v!r} s"
                                            for k, v in sec.items()),
              file=sys.stderr)
    nbytes = ctx.window.nbytes.get("cache_read")
    if not sec or not nbytes or sec.get("repro.mla.scan", 0.0) <= 0:
        return None
    rate = nbytes / sec["repro.mla.scan"]
    return 100.0 * rate / (float(ctx.peaks["hbm_GBps"]) * 1e9)
