"""Pack, unpack and placement of a contraction: device milliseconds per
dispatch of every device op in the traced window other than the
``pim_contract`` kernel."""

from bench import contraction


def read(ctx):
    t = ctx.trace
    if t is None or not t.dispatches:
        return None
    s = contraction.kernel_s(t)
    if not s:
        return None
    return (t.kernel_s + t.outside_s - s) * 1e3 / t.dispatches
