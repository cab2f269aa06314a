"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in percent."""

import statistics

import reduce_trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    t0, t1 = T.window(ctx.trace)
    busy = statistics.fmean(T.busy_s(o, t0, t1)
                            for o in ctx.trace["devices"].values())
    return 100.0 * (1.0 - busy / (t1 - t0))
