"""Device: the least time of the window's needed work at one chip's peaks
(``Context.least_s``) divided by the number of chips, over the time a chip
was busy in the window averaged over the chips (%). ``device_roofline``
divides the whole window's work by one chip's peaks and would read the
chip count times too high on a sharded cell."""


def read(ctx):
    if not ctx.planes or ctx.busy_s <= 0 or ctx.peaks is None:
        return None
    return ctx.least_s() / len(ctx.planes) / ctx.busy_s * 100.0
