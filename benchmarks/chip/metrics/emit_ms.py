"""Host milliseconds per serve round spent on the steps' outputs (span
``serve.emit``: appending tokens, finishing requests, prefill
completion with prefix registration and handoff), from the program's
``ServeStats.emit_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "emit_s", "rounds")
