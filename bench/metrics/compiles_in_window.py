"""Device: programs compiled, or loaded from the compilation cache, inside
the window. Warm-up should leave none."""


def read(ctx):
    return float(ctx.compiles)
