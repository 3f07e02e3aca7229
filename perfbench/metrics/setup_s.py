"""setup_s: process start to window start, on the host clock — imports,
staging, inputs made on the device, and one warm-up pass."""


def read(ctx):
    return ctx.setup_s
