"""Device milliseconds per decode step, from the trace
(``reduce_trace.step_ms``)."""

import reduce_trace


def read(ctx):
    return reduce_trace.step_ms(ctx, "decode")
