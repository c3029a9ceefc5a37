"""Device: percent of the traced window in which no op ran on the device."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * t.idle_share
