"""Whole-step model FLOP utilisation of all computed tokens, prefill
and decode, in percent (``flops.mfu``)."""

import flops


def read(ctx):
    return flops.mfu(ctx, ("prefill", "decode"))
