"""Decoder-only LM assembly: embedding (decoupled gather), scan-over-layer
segments, LM head, loss, and the KV-cache decode step."""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dae_gather.ops import dae_gather
from repro.models.blocks import (block_apply, block_cache_init,
                                 block_cache_init_paged, block_init)
from repro.models.common import (ModelConfig, cross_entropy_loss, dense_init,
                                 on_each_device, rmsnorm, rmsnorm_init)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack_init(cfg: ModelConfig, kind: str, count: int, key) -> Params:
    keys = jax.random.split(key, count)
    return jax.vmap(lambda k: block_init(cfg, kind, k))(keys)


def lm_init(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, len(cfg.layer_specs()) + 3)
    params: Params = {
        "embed": dense_init(ks[0], cfg.vocab, cfg.d_model, cfg.pdtype),
        "final_norm": rmsnorm_init(cfg.d_model, cfg.pdtype),
        "segments": [
            _stack_init(cfg, spec.kind, spec.count, ks[i + 1])
            for i, spec in enumerate(cfg.layer_specs())
        ],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[-1], cfg.d_model, cfg.vocab,
                                       cfg.pdtype)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, params: Params, tokens: jnp.ndarray
                 ) -> jnp.ndarray:
    """Vocab-table gather — the framework's dae_gather hook."""
    b, s = tokens.shape
    if cfg.kernel_mode == "pallas":
        flat = on_each_device(cfg, dae_gather, params["embed"],
                              tokens.reshape(-1).astype(jnp.int32))
        return flat.reshape(b, s, cfg.d_model).astype(cfg.adtype)
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.adtype)


def _sp_constraint(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Sequence-parallel residual stream (Megatron SP): between blocks the
    activations shard their token axis over the TP axis, turning the
    row-parallel all-reduce into reduce-scatter + all-gather (half the
    link bytes, overlappable)."""
    if not cfg.act_sp:
        return x
    from jax.sharding import PartitionSpec as P
    dp = cfg.mesh_dp_axes if len(cfg.mesh_dp_axes) > 1 else \
        cfg.mesh_dp_axes[0]
    return jax.lax.with_sharding_constraint(
        x, P(dp, cfg.mesh_tp_axis, None))


def _segment_scan(cfg: ModelConfig, kind: str, stacked: Params,
                  x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    def body(h, layer_params):
        h = _sp_constraint(cfg, h)
        h2, _ = block_apply(cfg, kind, layer_params, h, positions)
        h2 = _sp_constraint(cfg, h2)
        return h2, None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, policy=policy)
    if not cfg.scan_layers:  # unrolled: used by the dry-run cost probes
        count = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(count):
            x, _ = body(x, jax.tree.map(lambda a: a[i], stacked))
        return x
    x, _ = jax.lax.scan(body, x, stacked)
    return x


def lm_apply(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
             positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens (B, S) -> logits (B, S, V)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = embed_tokens(cfg, params, tokens)
    for spec, stacked in zip(cfg.layer_specs(), params["segments"]):
        x = _segment_scan(cfg, spec.kind, stacked, x, positions)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    w_out = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = x @ w_out.astype(cfg.adtype)
    if cfg.logit_soft_cap:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    return logits


def lm_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> jnp.ndarray:
    logits = lm_apply(cfg, params, batch["tokens"])
    return cross_entropy_loss(logits, batch["labels"])


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def lm_cache_init(cfg: ModelConfig, batch: int, s_max: int) -> List[Any]:
    caches = []
    for spec in cfg.layer_specs():
        one = block_cache_init(cfg, spec.kind, batch, s_max)
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (spec.count,) + a.shape), one))
    return caches


def lm_cache_init_paged(cfg: ModelConfig, batch: int, n_pages: int,
                        page: int) -> List[Any]:
    """Paged decode caches: KV pages are pooled across all ``batch``
    slots; each layer of a segment gets its own pool (leaf shape
    ``(count, n_pages, ...)``) addressed by one shared page table."""
    caches = []
    for spec in cfg.layer_specs():
        one = block_cache_init_paged(cfg, spec.kind, batch, n_pages, page)
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (spec.count,) + a.shape), one))
    return caches


_PAGE_KEYS = ("kp", "vp", "ckvp", "krp")


def lm_copy_pages(caches: List[Any], src: jnp.ndarray, dst: jnp.ndarray
                  ) -> List[Any]:
    """Copy physical page ``src`` into page ``dst`` in every layer —
    the allocator's copy-on-write primitive.  src/dst are int32 scalars
    (traced, so one jit covers every page pair)."""
    out = []
    for cache in caches:
        new = dict(cache)
        attn = dict(cache["attn"])
        for key in _PAGE_KEYS:
            if key in attn:
                a = attn[key]
                attn[key] = a.at[:, dst].set(a[:, src])
        new["attn"] = attn
        out.append(new)
    return out


def lm_gather_pages(caches: List[Any], pages: jnp.ndarray) -> List[Any]:
    """Pull physical pages ``pages`` (NPB,) int32 out of every layer's
    pool: leaf (count, NP, ...) -> block (count, NPB, ...).  One half of
    the disaggregated prefill->decode migration — the blocks keep the
    pool layout, so the matching :func:`lm_scatter_pages` on another
    mesh is a pure placement move."""
    out = []
    for cache in caches:
        blk = {}
        attn = cache["attn"]
        for key in _PAGE_KEYS:
            if key in attn:
                blk[key] = jnp.take(attn[key], pages, axis=1)
        out.append(blk)
    return out


def lm_scatter_pages(caches: List[Any], blocks: List[Any],
                     pages: jnp.ndarray, slot: jnp.ndarray,
                     new_len: jnp.ndarray) -> List[Any]:
    """Write migrated ``blocks`` (from :func:`lm_gather_pages`) into
    physical pages ``pages`` of every layer's pool and set slot
    ``slot``'s logical length to ``new_len``.  Page lists padded with
    page 0 (the allocator's reserved trash page) are safe: its contents
    are never attended."""
    out = []
    for cache, blk in zip(caches, blocks):
        new = dict(cache)
        attn = dict(cache["attn"])
        for key in _PAGE_KEYS:
            if key in attn:
                a = attn[key]
                attn[key] = a.at[:, pages].set(blk[key].astype(a.dtype))
        ln = attn["len"]
        onehot = jnp.arange(ln.shape[1]) == slot
        attn["len"] = jnp.where(onehot[None, :], new_len.astype(ln.dtype),
                                ln)
        new["attn"] = attn
        out.append(new)
    return out


def lm_paged_reset(caches: List[Any], keep: jnp.ndarray,
                   new_lens: jnp.ndarray) -> List[Any]:
    """Reset per-slot logical lengths for slots where ``keep`` is False
    (to ``new_lens``, e.g. a reused prefix length).  Page contents are
    untouched: positions < len are always freshly written by prefill
    and positions >= len are masked out of attention."""
    out = []
    for cache in caches:
        new = dict(cache)
        attn = dict(cache["attn"])
        ln = attn["len"]
        attn["len"] = jnp.where(keep[None, :], ln,
                                new_lens[None, :].astype(ln.dtype))
        new["attn"] = attn
        out.append(new)
    return out


def lm_decode_step(cfg: ModelConfig, params: Params, caches: List[Any],
                   token: jnp.ndarray, pos: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, List[Any]]:
    """One decode step: token (B,), pos (B,) -> (logits (B, V), caches)."""
    b = token.shape[0]
    positions = pos[:, None]
    x = embed_tokens(cfg, params, token[:, None])

    new_caches = []
    for spec, stacked, cache in zip(cfg.layer_specs(), params["segments"],
                                    caches):
        def body(h, pc):
            layer_params, layer_cache = pc
            h2, nc = block_apply(cfg, spec.kind, layer_params, h, positions,
                                 cache=layer_cache)
            return h2, nc

        if not cfg.scan_layers:
            ncs = []
            for i in range(spec.count):
                x, nci = body(x, jax.tree.map(lambda a: a[i], (stacked, cache)))
                ncs.append(nci)
            nc = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
        else:
            x, nc = jax.lax.scan(body, x, (stacked, cache))
        new_caches.append(nc)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    w_out = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x[:, 0] @ w_out.astype(cfg.adtype)).astype(jnp.float32)
    return logits, new_caches


def lm_prefill(cfg: ModelConfig, params: Params, caches: List[Any],
               tokens: jnp.ndarray, pos: jnp.ndarray, n_valid: jnp.ndarray,
               page_table: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, List[Any]]:
    """Chunked, batched, teacher-forced cache fill — the serving Access
    engine's step (paper §3: the decoupled access stream).

    tokens (B, C) int32 — the next C prompt tokens per slot; pos (B,) —
    each slot's current sequence position (== its cache length);
    n_valid (B,) — how many of the C tokens are real per slot (0 leaves
    that slot's cache, recurrent state and position untouched).

    Returns (logits (B, V) float32 taken at each slot's LAST VALID
    token, new caches).  A C=1 call with n_valid in {0, 1} is a masked
    decode step — the Execute engine uses exactly that, so prefill and
    decode share this one primitive (compiled once per chunk width).
    """
    b, c = tokens.shape
    positions = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]
    x = embed_tokens(cfg, params, tokens)

    new_caches = []
    for spec, stacked, cache in zip(cfg.layer_specs(), params["segments"],
                                    caches):
        def body(h, pc):
            layer_params, layer_cache = pc
            h2, nc = block_apply(cfg, spec.kind, layer_params, h, positions,
                                 cache=layer_cache, valid=valid,
                                 page_table=page_table)
            return h2, nc

        if not cfg.scan_layers:
            ncs = []
            for i in range(spec.count):
                x, nci = body(x, jax.tree.map(lambda a: a[i], (stacked, cache)))
                ncs.append(nci)
            nc = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
        else:
            # the stacked cache rides the scan carry and each layer's
            # slice is written back in place: as scan outputs, the new
            # cache would be a second buffer the size of the whole pool
            def layer(carry, layer_params, body=body):
                h, cache_all, i = carry
                h, nci = body(h, (layer_params,
                                  jax.tree.map(lambda a: a[i], cache_all)))
                cache_all = jax.tree.map(lambda a, n: a.at[i].set(n),
                                         cache_all, nci)
                return (h, cache_all, i + 1), None

            (x, nc, _), _ = jax.lax.scan(layer, (x, cache, 0), stacked)
        new_caches.append(nc)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.clip(n_valid - 1, 0, c - 1)[:, None, None]
    xl = jnp.take_along_axis(x, last, axis=1)[:, 0]            # (B, D)
    w_out = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (xl @ w_out.astype(cfg.adtype)).astype(jnp.float32)
    return logits, new_caches


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
