"""95th percentile of every gap between consecutive tokens of one
request that ends in the window, in milliseconds (host clock)."""

from stats import percentile, token_gaps


def read(ctx):
    gaps = token_gaps(ctx.stamps, ctx.t0, ctx.t1)
    return 1000.0 * percentile(gaps, 95) if len(gaps) >= 20 else None
