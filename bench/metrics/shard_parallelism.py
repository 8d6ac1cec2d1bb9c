"""Sharded fan-out: chips busy at once while any is busy. The sum over the
chips of each one's busy time in the window, over the time in which at
least one chip is busy: 1.0 when the chips take turns, the chip count when
all of them are always busy together."""
from bench.lib import trace


def read(ctx):
    if ctx.trace is None or not ctx.planes:
        return None
    per = [trace.union(trace.clip(ctx.trace.op_intervals(p), ctx.lo_ns,
                                  ctx.hi_ns)) for p in ctx.planes]
    any_busy = sum(e - s for s, e in trace.union(iv for u in per
                                                 for iv in u))
    if any_busy <= 0:
        return None
    return sum(e - s for u in per for s, e in u) / any_busy
