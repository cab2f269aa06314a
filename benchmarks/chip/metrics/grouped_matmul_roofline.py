"""Roofline share of the expert GEMM kernel, in percent: the least time
the chip could take for the traced steps' expert products (the larger of
their FLOPs over peak FLOP/s and the bytes of the held experts'
matrices, read once a step and layer, and of the rows in and out over
peak bandwidth), divided by the device time of the kernel's calls
(``%_gmm_impl.N`` custom-calls) in the trace.  Averaged over the chips.
The rows are the program's ``ServeStats.moe_rows`` over the traced
window; the FLOPs and bytes are the configuration's family's
``moe_gemm_cost``.  Nothing where the program keeps no such counter or
the family has no experts."""

import statistics

import reduce_trace as T

KERNEL = r"_gmm_impl(\.\d+)?$"


def read(ctx):
    cost = getattr(ctx.family, "moe_gemm_cost", None)
    if ctx.trace is None or cost is None or "moe_rows" not in ctx.c1:
        return None
    t0, t1 = T.window(ctx.trace)
    secs = [T.kernel_s(o, KERNEL, t0, t1)
            for o in ctx.trace["devices"].values()]
    secs = [s for s in secs if s]
    rows = ctx.delta("moe_rows")
    if not secs or not rows:
        return None
    f, b = cost(ctx.model, rows, len(ctx.steps_in()))
    least = max(f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / statistics.fmean(secs)
