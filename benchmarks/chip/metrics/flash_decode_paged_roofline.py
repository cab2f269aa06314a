"""Roofline share of the paged decode kernel, in percent: the least
time the chip could take for the traced decode steps' attention (the
larger of its FLOPs over peak FLOP/s and the bytes of the valid K/V
pages, the query and the output over peak bandwidth), divided by the
device time of the kernel's calls (``%_decode_paged_impl.N``
custom-calls) in the trace.  Averaged over the chips.  The FLOPs and
bytes are the configuration's family's ``paged_decode_cost``."""

import statistics

import reduce_trace as T

KERNEL = r"_decode_paged_impl(\.\d+)?$"


def read(ctx):
    if ctx.trace is None:
        return None
    t0, t1 = T.window(ctx.trace)
    secs = [T.kernel_s(o, KERNEL, t0, t1)
            for o in ctx.trace["devices"].values()]
    secs = [s for s in secs if s]
    if not secs:
        return None
    f = b = 0.0
    for _, _, _, lens, _ in ctx.steps_in("decode"):
        df, db = ctx.family.paged_decode_cost(ctx.model, lens,
                                              ctx.deployment["page"])
        f, b = f + df, b + db
    if not f:
        return None
    least = max(f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / statistics.fmean(secs)
