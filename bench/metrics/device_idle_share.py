"""Device: share of the traced window in which no operation ran on the
device, averaged over the chips (%)."""


def read(ctx):
    if not ctx.planes or ctx.window_s <= 0:
        return None
    return (1.0 - ctx.busy_s / ctx.window_s) * 100.0
