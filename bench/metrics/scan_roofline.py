"""Kernels: share of its roofline that the compiled pruned scan reaches (%).

The least time is the larger of the bytes and the flops a correct exact
answer needs over the published peaks (``Context.least_s``: each engine
call reads every row some query of it keeps once, and each query scores
every row it keeps); the time is the scan program's device time in the
window. The work is counted from the rows the answers need, not from what
the program executes, so a scan that does less wasted work reads higher."""
from bench.metrics.scan_device_ms_per_q import scan_seconds


def read(ctx):
    s = scan_seconds(ctx)
    if s is None or ctx.peaks is None:
        return None
    return ctx.least_s() / s * 100.0
