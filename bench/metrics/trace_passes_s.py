"""Compiler set-up: host seconds of ``fn.compiled(basis=...)`` (trace,
passes and lowering of the cell's program), timed in set-up."""


def read(ctx):
    return ctx.trace_passes_s
