"""Operations and bytes a dense decoder's serving work needs, from shapes.

These count what the algorithm requires, not what the program happens
to do: a prompt token at position p attends to p + 1 positions, a
decode step reads only the pages that hold a slot's tokens.
"""

from __future__ import annotations

from typing import Dict, Iterable


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies in one layer."""
    d, hd, h, kvh, f = (m["d_model"], m["head_dim"], m["n_heads"],
                        m["n_kv_heads"], m["d_ff"])
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return attn + mlp


def token_flops(m: Dict, position: int) -> float:
    """FLOPs of one token at ``position`` through every layer: the
    matrix products, and attention over ``position + 1`` keys."""
    per_layer = (2 * layer_matmul_params(m)
                 + 4 * m["n_heads"] * m["head_dim"] * (position + 1))
    return float(m["n_layers"] * per_layer)


def span_flops(m: Dict, start: int, n: int) -> float:
    """FLOPs of ``n`` consecutive tokens from ``start`` (closed form)."""
    if n <= 0:
        return 0.0
    attn_keys = n * start + n * (n + 1) / 2      # sum of (p + 1)
    return float(m["n_layers"] * (2 * layer_matmul_params(m) * n
                                  + 4 * m["n_heads"] * m["head_dim"]
                                  * attn_keys))


def head_flops(m: Dict, rows: int) -> float:
    """FLOPs of the unembedding for ``rows`` logit rows."""
    return float(2 * m["d_model"] * m["vocab"] * rows)


def paged_decode_cost(m: Dict, lengths: Iterable[int], page: int,
                      itemsize: int = 2):
    """(FLOPs, bytes) of one paged-decode kernel call per layer, summed
    over layers, for active slots whose queries see ``lengths`` keys:
    QK and PV products, and the K and V pages that hold those keys plus
    the query and output rows."""
    h, kvh, hd, layers = (m["n_heads"], m["n_kv_heads"], m["head_dim"],
                          m["n_layers"])
    flops = nbytes = 0.0
    for ln in lengths:
        flops += 4 * h * hd * ln
        pages = -(-ln // page)
        nbytes += 2 * pages * page * kvh * hd * itemsize + 2 * h * hd * itemsize
    return flops * layers, nbytes * layers


def mfu(ctx, kinds):
    """Model FLOPs of the tokens that steps of ``kinds`` computed in the
    window, in percent of the chips' peak over the window: each token's
    matrix products and causal attention, and the unembedding of each
    logits row."""
    total = 0.0
    for kind, _, _, lens, starts in ctx.steps_in():
        if kind not in kinds:
            continue
        for ln, st in zip(lens, starts):
            total += span_flops(ctx.model, st, ln - st)
        total += head_flops(ctx.model, len(lens))
    if not total:
        return None
    peak = ctx.cell["chips"] * ctx.peaks["flops_bf16"]
    return 100.0 * total / ctx.window_s / peak
