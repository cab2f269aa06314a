"""The MLA + MoE decoder family (DeepSeek-V2): per layer an RMSNorm,
multi-head latent attention (a query projection to ``n_heads`` heads of
``qk_nope_dim`` + ``qk_rope_dim``; a latent ``c_kv`` of ``kv_lora_rank``
with its RMSNorm; one rotated key of ``qk_rope_dim`` shared by the
heads; ``c_kv`` up-projected to each head's key and value), an RMSNorm
and a feed-forward: a dense SwiGLU of ``d_ff`` in the first
``first_dense_layers`` layers, then an expert layer (router over all
``n_experts``, ``n_experts_held`` experts of ``moe_d_ff`` held here,
``n_shared_experts`` shared experts as one SwiGLU of
``n_shared_experts * moe_d_ff``).  Two segments, as the program's init
lays them out: the dense layers, then the expert layers.

The counts are what the algorithm requires: a token at position p
attends to p + 1 positions with per-head keys and values; a routed token
meets ``top_k * n_experts_held / n_experts`` held experts on average
(the chip's share of a layer divided over ``n_experts / n_experts_held``
chips).  The decode kernel attends in latent space: each cached token's
``kv_lora_rank + qk_rope_dim`` values are read once, as K, and their
first ``kv_lora_rank`` serve as V.  The expert products
(``moe_gemm_cost``) count the rows the program routed to the held
experts (its ``moe_rows`` counter), each through the three matrices of
one expert, and each held expert's matrices read once a step and layer.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], int]

# rows of one block the program's expert layer gives grouped_matmul
# (``models/moe.BLOCK_ROWS``)
GMM_BLOCK_ROWS = 128


def _attn_leaves(m: Dict) -> List[Leaf]:
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return [(("ln1",), (d,), 0),
            (("attn", "wq"), (d, h * (dn + dr)), d),
            (("attn", "w_dkv"), (d, r), d),
            (("attn", "kv_norm"), (r,), 0),
            (("attn", "w_kr"), (d, dr), d),
            (("attn", "w_uk"), (r, h * dn), r),
            (("attn", "w_uv"), (r, h * dv), r),
            (("attn", "wo"), (h * dv, d), h * dv),
            (("ln2",), (d,), 0)]


def _swiglu(prefix, d: int, f: int) -> List[Leaf]:
    return [(prefix + ("w_gate",), (d, f), d), (prefix + ("w_up",), (d, f), d),
            (prefix + ("w_down",), (f, d), f)]


def dense_leaves(m: Dict) -> List[Leaf]:
    return _attn_leaves(m) + _swiglu(("mlp",), m["d_model"], m["d_ff"])


def moe_leaves(m: Dict) -> List[Leaf]:
    d, e, f = m["d_model"], m["n_experts_held"], m["moe_d_ff"]
    return _attn_leaves(m) + [
        (("moe", "router"), (d, m["n_experts"]), d),
        (("moe", "w_gate"), (e, d, f), d),
        (("moe", "w_up"), (e, d, f), d),
        (("moe", "w_down"), (e, f, d), f),
    ] + _swiglu(("moe", "shared"), d, m["n_shared_experts"] * f)


def segments(m: Dict) -> List[Tuple[int, List[Leaf]]]:
    k = m["first_dense_layers"]
    return [(k, dense_leaves(m)), (m["n_layers"] - k, moe_leaves(m))]


def outer_leaves(m: Dict) -> List[Tuple[str, Tuple[int, ...], int]]:
    d, v = m["d_model"], m["vocab"]
    out = [("embed", (v, d), d), ("final_norm", (d,), 0)]
    if not m["tie_embeddings"]:
        out.append(("unembed", (d, v), d))
    return out


def held_share(m: Dict) -> float:
    """Held experts a routed token meets on average."""
    return m["top_k"] * m["n_experts_held"] / m["n_experts"]


def attn_matmul_params(m: Dict) -> int:
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return (d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv
            + h * dv * d)


def ffn_matmul_params(m: Dict, moe: bool) -> float:
    """Weights one token multiplies in a layer's feed-forward; an expert
    layer counts the router, the expected held experts and the shared
    experts."""
    d = m["d_model"]
    if not moe:
        return 3 * d * m["d_ff"]
    f = m["moe_d_ff"]
    return (d * m["n_experts"] + held_share(m) * 3 * d * f
            + 3 * d * m["n_shared_experts"] * f)


def span_flops(m: Dict, start: int, n: int) -> float:
    """FLOPs of ``n`` consecutive tokens from ``start`` through every
    layer (closed form): the matrix products, and attention over
    ``p + 1`` keys at position p with per-head keys of ``qk_nope_dim +
    qk_rope_dim`` and values of ``v_head_dim``."""
    if n <= 0:
        return 0.0
    k, layers = m["first_dense_layers"], m["n_layers"]
    attn_keys = n * start + n * (n + 1) / 2      # sum of (p + 1)
    per_key = 2 * m["n_heads"] * (m["qk_nope_dim"] + m["qk_rope_dim"]
                                  + m["v_head_dim"])
    mm = (layers * attn_matmul_params(m) + k * ffn_matmul_params(m, False)
          + (layers - k) * ffn_matmul_params(m, True))
    return float(2 * mm * n + layers * per_key * attn_keys)


def head_flops(m: Dict, rows: int) -> float:
    """FLOPs of the unembedding for ``rows`` logit rows."""
    return float(2 * m["d_model"] * m["vocab"] * rows)


def paged_decode_cost(m: Dict, lengths: Iterable[int], page: int,
                      itemsize: int = 2):
    """(FLOPs, bytes) of one paged-decode kernel call per layer, summed
    over layers: all heads on the one latent head, scores over
    ``kv_lora_rank + qk_rope_dim`` and values over ``kv_lora_rank``; the
    bytes of the latent pages that hold the keys (each value once, the
    lanes the pool pads to 128 not counted), the query and the output."""
    h, r, dr, layers = (m["n_heads"], m["kv_lora_rank"], m["qk_rope_dim"],
                        m["n_layers"])
    flops = nbytes = 0.0
    for ln in lengths:
        flops += 2 * h * (2 * r + dr) * ln
        pages = -(-ln // page)
        nbytes += (pages * page * (r + dr) + h * (2 * r + dr)) * itemsize
    return flops * layers, nbytes * layers


def moe_gemm_cost(m: Dict, rows: int, steps: int, itemsize: int = 2):
    """(FLOPs, bytes) of the expert GEMM kernel's calls (gate, up, down
    in each expert layer) over ``steps`` model steps that routed ``rows``
    (token, expert) rows to the held experts in all: the rows' three
    products; each held expert's three matrices read once a step and
    layer, and each row's inputs and outputs."""
    d, f, e = m["d_model"], m["moe_d_ff"], m["n_experts_held"]
    moe_layers = m["n_layers"] - m["first_dense_layers"]
    flops = 2.0 * rows * 3 * d * f
    nbytes = (steps * moe_layers * e * 3 * d * f + rows * 3 * (d + f)
              ) * itemsize
    return flops, float(nbytes)
