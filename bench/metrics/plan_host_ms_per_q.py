"""Engine: host time in ``QueryEngine.plan`` (the Theorem 4.1 planner),
per answer delivered in the window (ms)."""


def read(ctx):
    if not ctx.served or "plan" not in ctx.host_s:
        return None
    return ctx.host_s["plan"] / ctx.served * 1e3
