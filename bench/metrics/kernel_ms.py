"""Executor kernel: device milliseconds per dispatch of the Mosaic custom
calls in the traced window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.dispatches or not t.kernel_s:
        return None
    return t.kernel_s * 1e3 / t.dispatches
