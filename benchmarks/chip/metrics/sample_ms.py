"""Host milliseconds per serve round spent choosing the decode step's
tokens (span ``serve.sample``: argmax over the fetched slots x vocab
logits), from the program's ``ServeStats.sample_s``."""

import counters


def read(ctx):
    return counters.per(ctx, "sample_s", "rounds")
