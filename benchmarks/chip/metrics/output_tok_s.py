"""Tokens emitted in the window / window seconds (host clock)."""


def read(ctx):
    n = sum(1 for ts in ctx.stamps.values() for t in ts
            if ctx.t0 <= t < ctx.t1)
    return n / ctx.window_s if n else None
