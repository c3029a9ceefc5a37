"""Compiler output: rows of the compiled gate schedule
(``CompiledSchedule.num_gates``), an exact count."""


def read(ctx):
    return ctx.compiled.num_gates
