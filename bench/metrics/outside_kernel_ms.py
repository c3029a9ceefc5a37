"""Pack, unpack and placement: device milliseconds per dispatch of every
device op in the traced window that is not an executor kernel."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.dispatches or not t.outside_s:
        return None
    return t.outside_s * 1e3 / t.dispatches
