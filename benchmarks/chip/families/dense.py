"""The dense decoder family: per layer an RMSNorm, attention with
``n_heads`` query heads on ``n_kv_heads`` KV heads (optionally a
per-head RMSNorm of q and k), an RMSNorm and a SwiGLU or GELU MLP; one
segment of ``n_layers`` such layers; the head tied to the embedding
where ``tie_embeddings`` is set.

The operation and byte counts are what the algorithm requires, not what
the program happens to do: a prompt token at position p attends to
p + 1 positions, a decode step reads only the pages that hold a slot's
tokens.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], int]


def layer_leaves(m: Dict) -> List[Leaf]:
    """(path, shape, fan_in) of one layer's leaves; fan_in 0 = norm gain."""
    d, hd, h, kvh = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"]
    out = [(("ln1",), (d,), 0),
           (("attn", "wq"), (d, h * hd), d),
           (("attn", "wk"), (d, kvh * hd), d),
           (("attn", "wv"), (d, kvh * hd), d),
           (("attn", "wo"), (h * hd, d), h * hd)]
    if m["qk_norm"]:
        out += [(("attn", "q_norm"), (hd,), 0), (("attn", "k_norm"), (hd,), 0)]
    out.append((("ln2",), (d,), 0))
    f = m["d_ff"]
    if m["mlp"] == "swiglu":
        out += [(("mlp", "w_gate"), (d, f), d), (("mlp", "w_up"), (d, f), d),
                (("mlp", "w_down"), (f, d), f)]
    else:
        out += [(("mlp", "w_up"), (d, f), d), (("mlp", "w_down"), (f, d), f)]
    return out


def segments(m: Dict) -> List[Tuple[int, List[Leaf]]]:
    return [(m["n_layers"], layer_leaves(m))]


def outer_leaves(m: Dict) -> List[Tuple[str, Tuple[int, ...], int]]:
    d, v = m["d_model"], m["vocab"]
    out = [("embed", (v, d), d), ("final_norm", (d,), 0)]
    if not m["tie_embeddings"]:
        out.append(("unembed", (d, v), d))
    return out


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies in one layer."""
    d, hd, h, kvh, f = (m["d_model"], m["head_dim"], m["n_heads"],
                        m["n_kv_heads"], m["d_ff"])
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return attn + mlp


def span_flops(m: Dict, start: int, n: int) -> float:
    """FLOPs of ``n`` consecutive tokens from ``start`` through every
    layer (closed form): the matrix products, and attention over
    ``p + 1`` keys at position p."""
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
