"""Host milliseconds per step, prefill or decode, spent handing the
step to the device (span ``step.dispatch``: the inputs' upload and the
jitted call until it returns), from the program's
``ServeStats.dispatch_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "dispatch_s", "prefill_steps", "decode_steps")
