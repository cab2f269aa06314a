"""Host milliseconds per serve round spent building the steps' inputs
(span ``serve.prepare``: handoff pops, decode page allocations, the
prefill grants and their copy-on-write dispatch, the token arrays),
from the program's ``ServeStats.prepare_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "prepare_s", "rounds")
