"""Executor kernel (contraction): device milliseconds per dispatch of the
ops named ``pim_contract*`` in the traced window, found by name, so work
moved into another kernel shows."""

from bench import contraction


def read(ctx):
    t = ctx.trace
    if t is None or not t.dispatches:
        return None
    s = contraction.kernel_s(t)
    return s * 1e3 / t.dispatches if s else None
