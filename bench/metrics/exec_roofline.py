"""Executor roofline share, in percent: the least time one dispatch could
take over the whole device busy time per dispatch.

The least time is the larger of the ops bound (the schedule's logic gates
times ceil(N/32) words, one word-op each, over the vector-unit peak) and
the bytes bound (the user arrays in and out over HBM bandwidth).  Dividing
by all device time, not the kernels' alone, keeps the reading about the
same work whatever implements it."""

import sys

from bench import reduce


def read(ctx):
    t = ctx.trace
    if t is None or not t.dispatches or not t.busy_s:
        return None
    least, bound = reduce.least_time(ctx.cost.gates, ctx.cell.elements,
                                     ctx.n_args, ctx.n_outputs, ctx.peaks)
    print(f"exec_roofline: bound={bound} least_s={least!r}", file=sys.stderr)
    return 100.0 * least / (t.busy_s / t.dispatches)
