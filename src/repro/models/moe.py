"""Mixture-of-experts layer with decoupled dispatch (paper §4.1 analogue).

The token→expert map after top-k routing is CSR-shaped: ``group offsets``
play the role of SPMV's ``rows`` array, and the expert GEMM stream is the
decoupled access stream.  Two dispatch paths:

* ``xla`` (default; used by the sharded dry-run): sort-based
  capacity-bounded dispatch — argsort tokens by expert, place the first
  C per expert into an (E, C) table, batched-einsum all experts, and
  scatter-add back with gate weights.  Shards cleanly with experts on
  the model axis (EP).

* ``pallas``: tokens sorted by expert and padded to block multiples,
  then the grouped_matmul kernel streams expert weight blocks via the
  scalar-prefetched block→expert map (the decoupled load of weights).

Both compute identical math up to capacity drops (the pallas path drops
nothing; tests compare against a no-drop oracle with ample capacity).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init, on_each_device
from repro.models.mlp import mlp_init, mlp_apply
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.common import round_up


def moe_init(cfg: ModelConfig, key) -> Dict[str, Any]:
    # expert weights may be padded so the expert dim divides the model
    # axis (EP); the router only ever routes to the real n_experts.
    e, d, f = cfg.n_experts_padded, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, cfg.pdtype),
        "w_gate": _expert_init(ks[1], e, d, f, cfg.pdtype),
        "w_up": _expert_init(ks[2], e, d, f, cfg.pdtype),
        "w_down": _expert_init(ks[3], e, f, d, cfg.pdtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(cfg, ks[4],
                               d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def _route(cfg: ModelConfig, p, x2d):
    """x2d (T, D) -> gates (T, K), experts (T, K)."""
    logits = (x2d @ p["router"].astype(cfg.adtype)).astype(jnp.float32)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def moe_apply(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray,
              *, capacity_factor: float = 0.0) -> jnp.ndarray:
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    gates, experts = _route(cfg, p, x2d)

    if cfg.kernel_mode == "pallas":
        y2d = on_each_device(
            cfg, lambda *a: _dispatch_pallas(cfg, *a), p, x2d, gates,
            experts)
    else:
        y2d = _dispatch_xla(cfg, p, x2d, gates, experts,
                            capacity_factor or cfg.capacity_factor)

    if cfg.n_shared_experts:
        y2d = y2d + mlp_apply(cfg, p["shared"], x2d)
    return y2d.reshape(b, s, d)


# -- xla sort-based capacity dispatch ----------------------------------------


def _dispatch_xla(cfg, p, x2d, gates, experts, capacity_factor):
    t, d = x2d.shape
    e, k = cfg.n_experts_padded, cfg.top_k
    c = int(max(1, math.ceil(t * k * capacity_factor / cfg.n_experts)))
    dt = cfg.adtype

    flat_e = experts.reshape(-1)                       # (T*K,)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    se, sg, stok = flat_e[order], flat_g[order], flat_tok[order]
    # position within expert group
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype), side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    keep = pos < c

    # (E, C) token table; dropped/empty slots point at the zero pad row
    table = jnp.full((e, c), t, jnp.int32)
    table = table.at[se, jnp.where(keep, pos, 0)].set(
        jnp.where(keep, stok, t), mode="drop")
    gtable = jnp.zeros((e, c), jnp.float32)
    gtable = gtable.at[se, jnp.where(keep, pos, 0)].set(
        jnp.where(keep, sg, 0.0), mode="drop")

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)])
    xe = jnp.take(x_pad, table, axis=0)                # (E, C, D)

    wg, wu, wd = (p["w_gate"].astype(dt), p["w_up"].astype(dt),
                  p["w_down"].astype(dt))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
    ye = jnp.einsum("ecf,efd->ecd", h, wd)             # (E, C, D)

    y = jnp.zeros((t + 1, d), jnp.float32)
    y = y.at[table.reshape(-1)].add(
        (ye * gtable[..., None]).reshape(-1, d).astype(jnp.float32))
    return y[:t].astype(x2d.dtype)


# -- pallas grouped-matmul dispatch -------------------------------------------


def _dispatch_pallas(cfg, p, x2d, gates, experts, bt: int = 128):
    t, d = x2d.shape
    e, k = cfg.n_experts_padded, cfg.top_k
    dt = cfg.adtype

    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    se, sg, stok = flat_e[order], flat_g[order], flat_tok[order]

    # pad each expert group to a multiple of bt: compute per-token slot in a
    # block-aligned layout
    counts = jnp.bincount(se, length=e)
    padded = ((counts + bt - 1) // bt) * bt
    block_starts = jnp.concatenate([jnp.zeros(1, padded.dtype),
                                    jnp.cumsum(padded)])[:-1]
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype), side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    slot = (block_starts[se] + pos).astype(jnp.int32)

    from repro.kernels.common import round_up as _ru
    tp = _ru(t * k, bt) + e * bt  # upper bound on padded length (static)
    xs = jnp.zeros((tp, d), x2d.dtype).at[slot].set(jnp.take(x2d, stok, 0))
    # block -> expert map
    nblocks = tp // bt
    block_first = jnp.arange(nblocks, dtype=jnp.int32) * bt
    block_expert = jnp.sum(block_first[:, None] >=
                           (block_starts + padded)[None, :], axis=1
                           ).astype(jnp.int32)
    block_expert = jnp.minimum(block_expert, e - 1)

    wg, wu, wd = (p["w_gate"].astype(dt), p["w_up"].astype(dt),
                  p["w_down"].astype(dt))
    h = jax.nn.silu(grouped_matmul(xs, wg, block_expert, bt=bt))
    h = h * grouped_matmul(xs, wu, block_expert, bt=bt)
    ys = grouped_matmul(h, wd, block_expert, bt=bt)    # (TP, D)

    contrib = jnp.take(ys, slot, axis=0).astype(jnp.float32) * sg[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[stok].add(contrib)
    return y.astype(x2d.dtype)


def moe_aux_loss(cfg: ModelConfig, p, x: jnp.ndarray) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style)."""
    b, s, d = x.shape
    x2d = x.reshape(-1, d)
    logits = (x2d @ p["router"].astype(cfg.adtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, experts = jax.lax.top_k(probs, cfg.top_k)
    me = probs.mean(0)
    ce = jnp.zeros(cfg.n_experts).at[experts.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    return cfg.n_experts * jnp.sum(me * ce)
