"""Compiler output for a contraction: rows of the step schedule that each
of its K steps runs (``CompiledSchedule.num_gates``), an exact count."""


def read(ctx):
    return ctx.compiled.num_gates
