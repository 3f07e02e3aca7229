"""append_share.mla: the share of the traced executions' device time
spent in the ops of the scope ``repro.mla.append``, the MLA decode
step's cache row writes: near 0 where the row lands in place, large
where XLA copies the cache to write it."""

from perfbench import scopes, trace


def read(ctx):
    sec = scopes.seconds(ctx.trace)
    total = trace.run_s(ctx.trace)
    if not sec or "repro.mla.scan" not in sec or total <= 0:
        return None
    return 100.0 * sec.get("repro.mla.append", 0.0) / total
