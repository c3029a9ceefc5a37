"""Contraction roofline share, in percent: the least time one dispatch of
``a [M, K] @ b [K, N]`` could take over the whole device busy time per
dispatch.

The least time is the larger of the ops bound (the step schedule's logic
gates times K times ceil(M*N/32) words, one word-op each, over the
vector-unit peak) and the bytes bound (A, B and C over HBM bandwidth);
``bench/contraction.py`` computes both.  Dividing by all device time, not
the kernel's alone, keeps the reading about the same work whatever
implements it."""

import sys

from bench import contraction


def read(ctx):
    t = ctx.trace
    mkn = contraction.shape(ctx.cell)
    if t is None or mkn is None or not t.dispatches or not t.busy_s:
        return None
    least, bound = contraction.least_time(ctx.cost.gates, *mkn, ctx.peaks)
    print(f"contract_roofline: bound={bound} least_s={least!r}",
          file=sys.stderr)
    return 100.0 * least / (t.busy_s / t.dispatches)
