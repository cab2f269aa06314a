"""Host milliseconds per step, prefill or decode, spent copying the
ready logits to the host (span ``step.copy``), from the program's
``ServeStats.copy_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "copy_s", "prefill_steps", "decode_steps")
