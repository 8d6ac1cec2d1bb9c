"""Kernels: the pruned scan's share of the roofline of all the cell's chips
together (%). The least time of the window's needed work at one chip's
peaks (``Context.least_s``) divided by the number of chips, over the scan
program's device time averaged over the chips. ``scan_roofline`` divides
the whole window's work by one chip's peaks and would read the chip count
times too high on a sharded cell."""
from bench.metrics.scan_device_ms_per_q import scan_seconds


def read(ctx):
    s = scan_seconds(ctx)
    if s is None or ctx.peaks is None:
        return None
    return ctx.least_s() / len(ctx.planes) / s * 100.0
