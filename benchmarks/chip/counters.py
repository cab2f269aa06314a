"""What the host-path readers compute from the program's ``ServeStats``
counters: one counter's difference across the window over the sum of
others' (rounds, steps, waits), or None where that sum is 0 or the
program keeps no such counter."""


def per(ctx, key: str, *over: str, scale: float = 1000.0):
    if key not in ctx.c1:
        return None
    n = sum(ctx.delta(k) for k in over)
    return scale * ctx.delta(key) / n if n else None
