"""stage_s: the seconds the program spent lowering and compiling (or
loading from its compile cache) the cell's timed executables, as its
staged records report them (``lower_seconds``, ``compile_seconds``)."""


def read(ctx):
    return ctx.stage_s
