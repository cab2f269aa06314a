"""Whole-step model FLOP utilisation, from the counts of the
configuration's family (``families/<family>.py``)."""

from __future__ import annotations


def mfu(ctx, kinds):
    """Model FLOPs of the tokens that steps of ``kinds`` computed in the
    window, in percent of the chips' peak over the window: each token's
    matrix products and causal attention, and the unembedding of each
    logits row."""
    fam, m = ctx.family, ctx.model
    total = 0.0
    for kind, _, _, lens, starts in ctx.steps_in():
        if kind not in kinds:
            continue
        for ln, st in zip(lens, starts):
            total += fam.span_flops(m, st, ln - st)
        total += fam.head_flops(m, len(lens))
    if not total:
        return None
    peak = ctx.cell["chips"] * ctx.peaks["flops_bf16"]
    return 100.0 * total / ctx.window_s / peak
