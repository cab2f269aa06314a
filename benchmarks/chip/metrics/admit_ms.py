"""Host milliseconds per serve round spent admitting requests (span
``serve.admit``: prefix lookup, reclaim, page reservation, the
slot-reset dispatch), from the program's ``ServeStats.admit_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "admit_s", "rounds")
