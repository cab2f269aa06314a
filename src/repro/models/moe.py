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

Cached serving, and any device told its ``expert_range`` (expert
parallelism: the device holds experts [lo, hi) of ``n_experts``), runs
the held-expert layer :func:`moe_apply_held`: routing over all experts,
only the held experts' part computed, nothing dropped — the pallas
dispatch restricted to the held experts, or a plain dense oracle in
``ref`` mode.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init, on_each_device
from repro.models.mlp import mlp_init, mlp_apply
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.common import round_up


# token rows of one grouped_matmul block
BLOCK_ROWS = 128


def moe_init(cfg: ModelConfig, key) -> Dict[str, Any]:
    # expert weights may be padded so the expert dim divides the model
    # axis (EP); the router only ever routes to the real n_experts.  A
    # device told its ``expert_range`` holds only those experts' weights.
    lo, hi = cfg.held_experts
    e, d, f = hi - lo, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
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
    """x2d (T, D) -> gates (T, K), experts (T, K): softmax over all
    ``n_experts``, the top ``top_k``, renormalised to sum 1 only where
    ``cfg.norm_topk_prob`` is set."""
    logits = (x2d @ p["router"].astype(cfg.adtype)).astype(jnp.float32)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def moe_apply(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray,
              *, capacity_factor: float = 0.0) -> jnp.ndarray:
    """The training / uncached expert layer: the capacity-bounded
    ``xla`` dispatch or the dropless ``pallas`` one.  A device told its
    ``expert_range`` always runs :func:`moe_apply_held`."""
    if cfg.expert_range is not None:
        return moe_apply_held(cfg, p, x)[0]
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


def moe_apply_held(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray,
                   valid: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The expert layer of a device that holds experts
    ``cfg.held_experts`` (all of them when no ``expert_range`` is set):
    routes every token over all ``n_experts``, computes the held
    experts' part of the result with nothing dropped, and adds the
    shared experts once.  Rows where ``valid`` (B, S) is False (a
    chunk's padding) are routed nowhere.  Cached serving runs this.

    ``pallas`` mode groups the held rows through ``grouped_matmul``
    (:func:`_dispatch_pallas`); ``ref`` mode is the plain oracle
    :func:`_dispatch_dense`.  Also returns int32 counters of the step:
    ``moe_rows``, the (token, held expert) rows, and ``moe_blocks``,
    the non-empty ``BLOCK_ROWS``-row blocks they fill."""
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    gates, experts = _route(cfg, p, x2d)
    valid = (jnp.ones((t,), bool) if valid is None else valid.reshape(t))
    lo, hi = cfg.held_experts
    counts = jnp.zeros((hi - lo + 1,), jnp.int32).at[
        _held_key(experts, valid, lo, hi).reshape(-1)].add(1)[:-1]
    stats = {"moe_rows": counts.sum(),
             "moe_blocks": ((counts + BLOCK_ROWS - 1) // BLOCK_ROWS).sum()}
    if cfg.kernel_mode == "pallas":
        y2d = on_each_device(cfg, lambda *a: _dispatch_pallas(cfg, *a), p,
                             x2d, gates, experts, valid)
    else:
        y2d = _dispatch_dense(cfg, p, x2d, gates, experts, valid)
    if cfg.n_shared_experts:
        y2d = y2d + mlp_apply(cfg, p["shared"], x2d)
    return y2d.reshape(b, s, d), stats


def _held_key(experts, valid, lo, hi):
    """(T, K) local expert id of each routed row, ``hi - lo`` where the
    expert is not held here or the row is not valid."""
    local = experts - lo
    held = (local >= 0) & (local < hi - lo) & valid[:, None]
    return jnp.where(held, local, hi - lo)


def _dispatch_dense(cfg, p, x2d, gates, experts, valid):
    """Plain oracle of the held experts' part: every held expert's
    SwiGLU over every token, weighted by the token's gate for it (0
    where it is not routed there)."""
    lo, hi = cfg.held_experts
    dt = cfg.adtype
    key = _held_key(experts, valid, lo, hi)                      # (T, K)
    w = (key[:, :, None] == jnp.arange(hi - lo)[None, None, :]) * gates[
        :, :, None]
    w = w.sum(1)                                                 # (T, E)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x2d, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("td,edf->tef", x2d, p["w_up"].astype(dt))
    ye = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(dt))
    y = jnp.einsum("ted,te->td", ye.astype(jnp.float32), w)
    return y.astype(x2d.dtype)


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


def _dispatch_pallas(cfg, p, x2d, gates, experts, valid=None,
                     bt: int = BLOCK_ROWS):
    """Dropless dispatch of the rows routed to the held experts through
    ``grouped_matmul``: rows sorted by expert, each expert's group
    padded to a multiple of ``bt``, one expert id per block.  The
    buffer is sized for the worst case (every token's ``top_k`` rows
    held here), and the products run twice as many blocks a call as
    there are held experts, in a loop over the non-empty blocks only:
    a step whose rows are few (a chunk of mostly padding) pays for one
    call, not for the worst case.  Both ends are gathers: each block
    gathers its rows' tokens as it runs, and each token gathers its
    ``top_k`` results back."""
    t, d = x2d.shape
    lo, hi = cfg.held_experts
    e, k = hi - lo, cfg.top_k
    dt = cfg.adtype
    if valid is None:
        valid = jnp.ones((t,), bool)

    flat_e = _held_key(experts, valid, lo, hi).reshape(-1)   # e = not here
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    here = se < e
    se_c = jnp.minimum(se, e - 1)

    # pad each expert group to a multiple of bt: compute per-token slot in a
    # block-aligned layout
    counts = jnp.zeros((e + 1,), jnp.int32).at[se].add(1)[:e]
    padded = ((counts + bt - 1) // bt) * bt
    block_starts = jnp.concatenate([jnp.zeros(1, padded.dtype),
                                    jnp.cumsum(padded)])[:-1]
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype), side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se_c]
    slot = (block_starts[se_c] + pos).astype(jnp.int32)

    # upper bound on the padded length (static): a token meets at most
    # min(k, e) held experts
    tp = round_up(t * min(k, e), bt) + e * bt
    seg = min(2 * e, tp // bt)
    tp = round_up(tp, seg * bt)
    # the token of each buffer row; t (a zero row) where none
    row_tok = jnp.full((tp,), t, jnp.int32).at[jnp.where(here, slot, tp)].set(
        (order // k).astype(jnp.int32), mode="drop")
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)])
    # block -> expert map
    nblocks = tp // bt
    block_first = jnp.arange(nblocks, dtype=jnp.int32) * bt
    block_expert = jnp.sum(block_first[:, None] >=
                           (block_starts + padded)[None, :], axis=1
                           ).astype(jnp.int32)
    block_expert = jnp.minimum(block_expert, e - 1)

    wg, wu, wd = (p["w_gate"].astype(dt), p["w_up"].astype(dt),
                  p["w_down"].astype(dt))

    def swiglu(tok, be):
        xb = jnp.take(x_pad, tok, axis=0)
        h = jax.nn.silu(grouped_matmul(xb, wg, be, bt=bt))
        h = h * grouped_matmul(xb, wu, be, bt=bt)
        return grouped_matmul(h, wd, be, bt=bt)

    if seg == nblocks:
        ys = swiglu(row_tok, block_expert)             # (TP, D)
    else:
        rows = seg * bt

        def body(i, ys):
            tok = jax.lax.dynamic_slice_in_dim(row_tok, i * rows, rows)
            be = jax.lax.dynamic_slice_in_dim(block_expert, i * seg, seg)
            return jax.lax.dynamic_update_slice_in_dim(ys, swiglu(tok, be),
                                                       i * rows, 0)

        live = padded.sum() // bt
        ys = jax.lax.fori_loop(0, (live + seg - 1) // seg, body,
                               jnp.zeros((tp, d), x2d.dtype))

    # each (token, k) row's buffer row, in token order; rows not held
    # here read row 0 and contribute nothing
    slot_tk = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.where(here, slot, 0))
    gate_tk = jnp.where(jnp.zeros((t * k,), bool).at[order].set(here),
                        gates.reshape(-1), 0.0)
    yk = jnp.take(ys, slot_tk, axis=0).reshape(t, k, d).astype(jnp.float32)
    return (yk * gate_tk.reshape(t, k, 1)).sum(1).astype(x2d.dtype)


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
