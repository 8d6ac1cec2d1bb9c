"""Device: the least time of the window's needed work (as for
``scan_roofline``) over the time the device was busy in the window (%).
It bounds ``scan_roofline`` from the whole device's side, so it still reads
when a later program no longer has the scan under its present name."""


def read(ctx):
    if not ctx.planes or ctx.busy_s <= 0 or ctx.peaks is None:
        return None
    return ctx.least_s() / ctx.busy_s * 100.0
