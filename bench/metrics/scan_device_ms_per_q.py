"""Route: device time of the pruned-scan program, summed from its events in
the profile and averaged over the chips, per answer delivered in the
window (ms)."""
from bench.lib import trace

PATTERN = r"_pruned_search_variant"


def scan_seconds(ctx):
    """Mean over the chips of the scan program's device seconds in the
    window, or None where the profile holds no such program."""
    if ctx.trace is None or not ctx.planes:
        return None
    per = [trace.matched_ns(ctx.trace, p, trace.MODULE_LINE, PATTERN,
                            ctx.lo_ns, ctx.hi_ns) for p in ctx.planes]
    s = sum(per) / len(per) * 1e-9
    return s if s > 0 else None


def read(ctx):
    s = scan_seconds(ctx)
    if s is None or not ctx.served:
        return None
    return s / ctx.served * 1e3
