"""Serving front end: host time in the server's ``step`` outside
``QueryEngine.execute`` (admission, round forming, grouping, answer
bookkeeping), per answer delivered in the window (ms)."""


def read(ctx):
    if not ctx.served or "step" not in ctx.host_s:
        return None
    own = ctx.host_s["step"] - ctx.host_s.get("execute", 0.0)
    return own / ctx.served * 1e3
