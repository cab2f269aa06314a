"""Milliseconds a request waited in the admit queue before a slot took
it, averaged over the requests taken (a preempted request counts
again), from the program's ``ServeStats.queue_wait_s`` and
``queue_waits``."""

import counters


def read(ctx):
    return counters.per(ctx, "queue_wait_s", "queue_waits")
