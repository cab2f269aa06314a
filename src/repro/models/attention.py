"""Attention layers: GQA (bias/qk-norm/sliding-window options) and MLA.

Both run in three modes:
  * prefill (full sequence, causal or bidirectional) — flash kernel or ref;
  * decode (one token against a KV cache);
  * cross-attention (encoder-decoder).

The KV block stream of the flash kernel is the decoupled-load path
(docs/architecture.md §"TPU adaptation"); MLA caches the *compressed
latent* so the decoupled
fetch reads kv_lora_rank + rope_dim bytes per token instead of
2 * KVH * head_dim.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, dense_init, on_each_device,
                                 rmsnorm, rmsnorm_init, rope)
from repro.kernels.flash_attention.ops import (flash_attention, flash_decode,
                                               flash_decode_paged)
from repro.kernels.flash_attention.ref import (NEG_INF, attention_banded,
                                               attention_chunked,
                                               attention_ref,
                                               decode_chunk_ref, decode_ref)


def _prefill_attention(cfg: ModelConfig, q, k, v, *, causal, window):
    """Dispatch: Pallas flash kernel / banded window / chunked online-
    softmax / naive S^2.  ``unroll`` follows cfg.scan_layers so the
    dry-run cost probes count every chunk."""
    if cfg.kernel_mode == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window)
    unroll = not cfg.scan_layers
    if cfg.attn_impl == "banded" and window and causal:
        return attention_banded(q, k, v, window=window, causal=True,
                                chunk=min(cfg.attn_chunk, window),
                                unroll=unroll)
    if cfg.attn_impl in ("banded", "chunked"):
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 chunk=cfg.attn_chunk, unroll=unroll)
    return attention_ref(q, k, v, causal=causal, window=window)


def _chunk_attention(cfg: ModelConfig, q, k, v, qlens):
    """Dispatch the chunked cache-fill attention: the grouped GEMM in
    ``pallas`` mode, the per-query oracle (bit-identical to stepwise
    decode) in ``ref`` mode."""
    if cfg.kernel_mode == "pallas":
        return grouped_chunk_attention(q, k, v, qlens)
    return decode_chunk_ref(q, k, v, qlens)


# One row block's float32 scores and probabilities, together, in bytes.
_CHUNK_SCORE_BYTES = 256 << 20


def grouped_chunk_attention(q, k, v, qlens):
    """Chunk queries against a cache as one grouped GEMM per KV head.

    q (B,H,C,D); k, v (B,KVH,S,D); qlens (B,C) — query i of row b
    attends cache positions < qlens[b, i].  The same mask, float32
    softmax and floor as :func:`decode_chunk_ref`, but the G = H/KVH
    query heads of a KV head and the C queries are the G·C rows of one
    matrix product with K (no repeated K/V, no loop over queries), so
    it runs on the MXU.  bfloat16 q·K products accumulate in float32;
    any float32 operand, and p·V always, runs at HIGHEST precision.
    Rows go in blocks whose scores fit ``_CHUNK_SCORE_BYTES``.
    """
    b, h, c, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    g = h // kvh
    hi = jax.lax.Precision.HIGHEST
    scale = d ** -0.5

    def row(qr, kr, vr, lr):          # (KVH,G,C,D), (KVH,S,D) x2, (C,)
        if qr.dtype == kr.dtype == jnp.bfloat16:
            sc = jnp.einsum("kgcd,ksd->kgcs", qr, kr,
                            preferred_element_type=jnp.float32)
        else:
            sc = jnp.einsum("kgcd,ksd->kgcs", qr.astype(jnp.float32),
                            kr.astype(jnp.float32), precision=hi)
        mask = jnp.arange(s)[None, :] < lr[:, None]                # (C, S)
        sc = jnp.where(mask, sc * scale, NEG_INF)
        p = jnp.exp(sc - sc.max(-1, keepdims=True))
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return jnp.einsum("kgcs,ksd->kgcd", p, vr.astype(jnp.float32),
                          precision=hi)

    row_bytes = 2 * 4 * h * c * s       # float32 scores + probabilities
    rows = max(1, min(b, _CHUNK_SCORE_BYTES // row_bytes))
    out = jax.lax.map(lambda a: row(*a),
                      (q.reshape(b, kvh, g, c, d), k, v, qlens),
                      batch_size=rows)
    return out.reshape(b, h, c, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    hd, h, kvh, d = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, h * hd, cfg.pdtype),
        "wk": dense_init(ks[1], d, kvh * hd, cfg.pdtype),
        "wv": dense_init(ks[2], d, kvh * hd, cfg.pdtype),
        "wo": dense_init(ks[3], h * hd, d, cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((kvh * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((kvh * hd,), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, cfg.pdtype)
        p["k_norm"] = rmsnorm_init(hd, cfg.pdtype)
    return p


def _project_qkv(cfg: ModelConfig, p, x, positions):
    b, s, d = x.shape
    hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    dt = cfg.adtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta)
    k = rope(k.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta)
    v = v.transpose(0, 2, 1, 3)
    return q, k, v      # (B, H, S, hd), (B, KVH, S, hd) x2


def gqa_apply(cfg: ModelConfig, p, x, positions, *, causal: bool = True,
              window: Optional[int] = None,
              cache: Optional[Dict[str, Any]] = None,
              valid: Optional[jnp.ndarray] = None,
              page_table: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]]]:
    """Prefill path when cache is None; decode path updates the cache.

    cache = {"k": (B,KVH,Smax,hd), "v": ..., "len": (B,) int32}
      or the paged layout
    cache = {"kp": (NP,KVH,PAGE,hd), "vp": ..., "len": (B,) int32}

    With a cache and S > 1 (or an explicit ``valid`` (B, S) mask) this is
    the *chunked cache-fill* path: the S new tokens of each batch row are
    scattered at its ``cache["len"]``-onward positions, query i attends
    the prefix through position len+i, and rows whose ``valid`` count is
    0 leave both cache and length untouched — the serving loop's Access
    (prefill-chunk) and Execute (masked decode) engines both land here.

    The paged layout stores KV in a shared pool of fixed-size pages;
    ``page_table`` (B, NPB) int32 maps each row's logical block i to a
    pool page.  Invalid-token scatters are routed to the reserved trash
    page 0 (see runtime.serve_loop.PageAllocator).  In ``pallas`` mode a
    single-token step drives ``flash_decode_paged``'s ring gather over
    the scalar-prefetched table; the ref path gathers the table back to
    a contiguous (B, KVH, NPB*PAGE, hd) view and reuses the exact
    contiguous oracle, so paged and contiguous decode are bit-identical
    whenever NPB*PAGE equals the contiguous s_max.

    A chunk (S > 1, either layout) attends through
    :func:`grouped_chunk_attention` in ``pallas`` mode, one grouped GEMM
    per KV head over the (gathered) cache; ``ref`` mode keeps the
    per-query oracle :func:`decode_chunk_ref`, which the grouped path
    matches within float32 rounding, not bit for bit.
    """
    b, s, d = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)

    if cache is None:
        out = _prefill_attention(cfg, q, k, v, causal=causal, window=window)
        new_cache = None
    elif "kp" in cache:
        if page_table is None:
            raise ValueError("paged KV cache requires a page_table")
        pos = cache["len"]                                     # (B,)
        if valid is None:
            valid = jnp.ones((b, s), bool)
        kp = _pool_constraint(cfg, _scatter_chunk_pages(
            cache["kp"], k, pos, valid, page_table))
        vp = _pool_constraint(cfg, _scatter_chunk_pages(
            cache["vp"], v, pos, valid, page_table))
        lens = pos + valid.sum(-1).astype(pos.dtype)
        qlens = pos[:, None] + jnp.arange(1, s + 1, dtype=pos.dtype)[None]
        if s == 1 and cfg.kernel_mode == "pallas":
            out = on_each_device(cfg, flash_decode_paged, q[:, :, 0, :],
                                 kp, vp, page_table, qlens[:, 0])
            out = out[:, :, None, :]
        else:
            kc = _gather_pages(kp, page_table)
            vc = _gather_pages(vp, page_table)
            out = _chunk_attention(cfg, q, kc, vc, qlens)       # (B,H,S,hd)
        new_cache = {"kp": kp, "vp": vp, "len": lens}
    elif s == 1 and valid is None:
        pos = cache["len"]                                     # (B,)
        # scatter the new K/V at each batch row's position
        kc = _scatter_token(cache["k"], k, pos)
        vc = _scatter_token(cache["v"], v, pos)
        lens = pos + 1
        qd = q[:, :, 0, :]                                     # (B,H,hd)
        if cfg.kernel_mode == "pallas":
            out = flash_decode(qd, kc, vc, lens)
        else:
            out = decode_ref(qd, kc, vc, lens)
        if window is not None:
            pass  # window decode handled by length mask upstream for now
        out = out[:, :, None, :]                               # (B,H,1,hd)
        new_cache = {"k": kc, "v": vc, "len": lens}
    else:
        pos = cache["len"]                                     # (B,)
        if valid is None:
            valid = jnp.ones((b, s), bool)
        kc = _scatter_chunk(cache["k"], k, pos, valid)
        vc = _scatter_chunk(cache["v"], v, pos, valid)
        lens = pos + valid.sum(-1).astype(pos.dtype)
        # query i of row b sees cache positions < pos_b + i + 1 (window
        # decode stays length-masked, matching the single-token path)
        qlens = pos[:, None] + jnp.arange(1, s + 1, dtype=pos.dtype)[None]
        if s == 1 and cfg.kernel_mode == "pallas":
            # masked decode keeps the optimized decode kernel (masked
            # rows produce garbage that the caller never reads)
            out = flash_decode(q[:, :, 0, :], kc, vc, qlens[:, 0])
            out = out[:, :, None, :]
        else:
            out = _chunk_attention(cfg, q, kc, vc, qlens)       # (B,H,S,hd)
        new_cache = {"k": kc, "v": vc, "len": lens}

    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    out = out @ p["wo"].astype(cfg.adtype)
    return out, new_cache


def _scatter_token(cache: jnp.ndarray, new: jnp.ndarray,
                   pos: jnp.ndarray) -> jnp.ndarray:
    """cache (B, KVH, Smax, hd); new (B, KVH, 1, hd); pos (B,)."""
    smax = cache.shape[2]
    onehot = (jnp.arange(smax)[None, :] == pos[:, None])       # (B, Smax)
    upd = onehot[:, None, :, None] * new.astype(cache.dtype)
    keep = jnp.where(onehot[:, None, :, None], 0, 1).astype(cache.dtype)
    return cache * keep + upd


def _scatter_chunk(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
                   valid: jnp.ndarray) -> jnp.ndarray:
    """cache (B, KVH, Smax, hd); new (B, KVH, C, hd); pos (B,);
    valid (B, C).  Chunk token i of row b lands at position pos_b + i;
    invalid tokens write nothing."""
    smax, c = cache.shape[2], new.shape[2]
    tgt = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]   # (B, C)
    onehot = ((tgt[:, :, None] == jnp.arange(smax)[None, None, :])
              & valid[:, :, None])                             # (B, C, Smax)
    oh = onehot.astype(cache.dtype)
    upd = jnp.einsum("bcs,bkcd->bksd", oh, new.astype(cache.dtype))
    keep = (1 - oh.sum(1))[:, None, :, None]                   # (B,1,Smax,1)
    return cache * keep + upd


# paged KV helpers ------------------------------------------------------------


def _page_targets(page: int, npb: int, pos, valid):
    """(page id is resolved by the caller) logical block + offset of each
    of the C new tokens per row; invalid tokens are rerouted to block 0
    (the allocator's reserved trash page)."""
    c = valid.shape[1]
    tgt = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]   # (B, C)
    blk = jnp.clip(tgt // page, 0, npb - 1)
    return blk, tgt % page


def _pool_constraint(cfg: ModelConfig, pages: jnp.ndarray) -> jnp.ndarray:
    """Sharded paged serving: keep the page pool's page dim (dim 0 of
    the per-layer (NP, ...) view) on ``cfg.mesh_pool_axis`` across the
    scatter, so jit propagation cannot re-replicate the pool after each
    update (the pool dominates serve memory).  Follows the
    ``_sp_constraint`` precedent in transformer.py — needs an ambient
    mesh when set."""
    if cfg.mesh_pool_axis is None:
        return pages
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        pages, P(cfg.mesh_pool_axis, *([None] * (pages.ndim - 1))))


def _scatter_chunk_pages(pages: jnp.ndarray, new: jnp.ndarray,
                         pos: jnp.ndarray, valid: jnp.ndarray,
                         page_table: jnp.ndarray) -> jnp.ndarray:
    """pages (NP, KVH, PAGE, hd); new (B, KVH, C, hd); pos (B,);
    valid (B, C); page_table (B, NPB) int32.  Valid token i of row b
    lands at offset (pos_b + i) % PAGE of page
    table[b, (pos_b + i) // PAGE]; invalid tokens land in page 0, whose
    contents are never attended (lengths mask them)."""
    page = pages.shape[2]
    kvh, hd = pages.shape[1], pages.shape[3]
    blk, off = _page_targets(page, page_table.shape[1], pos, valid)
    pg = jnp.where(valid, jnp.take_along_axis(page_table, blk, axis=1), 0)
    vals = new.transpose(0, 2, 1, 3).reshape(-1, kvh, hd)      # (B*C, KVH, hd)
    return pages.at[pg.reshape(-1), :, off.reshape(-1), :].set(
        vals.astype(pages.dtype))


def _scatter_vec_pages(pages: jnp.ndarray, new: jnp.ndarray,
                       pos: jnp.ndarray, valid: jnp.ndarray,
                       page_table: jnp.ndarray) -> jnp.ndarray:
    """pages (NP, PAGE, D); new (B, C, D) — the MLA latent variant."""
    page = pages.shape[1]
    blk, off = _page_targets(page, page_table.shape[1], pos, valid)
    pg = jnp.where(valid, jnp.take_along_axis(page_table, blk, axis=1), 0)
    return pages.at[pg.reshape(-1), off.reshape(-1), :].set(
        new.reshape(-1, new.shape[-1]).astype(pages.dtype))


def _gather_pages(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """(NP, KVH, PAGE, hd), (B, NPB) -> contiguous (B, KVH, NPB*PAGE, hd)."""
    g = jnp.take(pages, page_table, axis=0)        # (B, NPB, KVH, PAGE, hd)
    b, npb, kvh, page, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, kvh, npb * page, hd)


def _gather_vec_pages(pages: jnp.ndarray, page_table: jnp.ndarray
                      ) -> jnp.ndarray:
    """(NP, PAGE, D), (B, NPB) -> contiguous (B, NPB*PAGE, D)."""
    g = jnp.take(pages, page_table, axis=0)        # (B, NPB, PAGE, D)
    b, npb, page, d = g.shape
    return g.reshape(b, npb * page, d)


# cross attention (enc-dec) ---------------------------------------------------


def cross_attn_apply(cfg: ModelConfig, p, x, enc_kv, positions,
                     per_query: bool = False):
    """x (B,S,D) queries; enc_kv precomputed (k, v) (B,KVH,Senc,hd).

    ``per_query`` (serving's chunked cache-fill path) computes the S
    queries sequentially with S=1 shapes so the result is bit-identical
    to S single-token decode steps — see decode_chunk_ref for why."""
    b, s, d = x.shape
    hd, h = cfg.hd, cfg.n_heads
    dt = cfg.adtype
    q = (x @ p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k, v = enc_kv
    if per_query:
        out = jax.lax.map(
            lambda qi: _prefill_attention(cfg, qi[:, :, None], k, v,
                                          causal=False, window=None),
            q.transpose(2, 0, 1, 3))                   # (S,B,H,1,hd)
        out = out[:, :, :, 0].transpose(1, 2, 0, 3)    # (B,H,S,hd)
    else:
        out = _prefill_attention(cfg, q, k, v, causal=False, window=None)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return out @ p["wo"].astype(dt)


def cross_kv(cfg: ModelConfig, p, enc_out):
    b, se, d = enc_out.shape
    kvh, hd = cfg.n_kv_heads, cfg.hd
    dt = cfg.adtype
    k = (enc_out @ p["wk"].astype(dt))
    v = (enc_out @ p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    k = k.reshape(b, se, kvh, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, se, kvh, hd).transpose(0, 2, 1, 3)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 / MiniCPM3 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope, cfg.qk_rope_dim, cfg.v_hd
    r = cfg.kv_lora_rank
    p: Dict[str, Any] = {
        "w_dkv": dense_init(ks[0], d, r, cfg.pdtype),          # latent down
        "kv_norm": rmsnorm_init(r, cfg.pdtype),
        "w_uk": dense_init(ks[1], r, h * dn, cfg.pdtype),      # k up (nope)
        "w_uv": dense_init(ks[2], r, h * dv, cfg.pdtype),      # v up
        "w_kr": dense_init(ks[3], d, dr, cfg.pdtype),          # shared k rope
        "wo": dense_init(ks[4], h * dv, d, cfg.pdtype),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], d, cfg.q_lora_rank, cfg.pdtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, cfg.pdtype)
        p["w_uq"] = dense_init(ks[6], cfg.q_lora_rank, h * (dn + dr), cfg.pdtype)
    else:
        p["wq"] = dense_init(ks[7], d, h * (dn + dr), cfg.pdtype)
    return p


def _mla_q(cfg, p, x):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope, cfg.qk_rope_dim
    dt = cfg.adtype
    if cfg.q_lora_rank:
        ql = rmsnorm(x @ p["w_dq"].astype(dt), p["q_norm"], cfg.norm_eps)
        q = ql @ p["w_uq"].astype(dt)
    else:
        q = x @ p["wq"].astype(dt)
    q = q.reshape(b, s, h, dn + dr)
    return q[..., :dn], q[..., dn:]            # nope (B,S,H,dn), rope (B,S,H,dr)


def mla_apply(cfg: ModelConfig, p, x, positions, *, causal: bool = True,
              cache: Optional[Dict[str, Any]] = None,
              valid: Optional[jnp.ndarray] = None,
              page_table: Optional[jnp.ndarray] = None):
    """MLA attention.  cache = {"ckv": (B,Smax,r), "kr": (B,Smax,dr),
    "len": (B,)} — the compressed-latent cache (the MLA memory win) —
    or the paged layout {"ckvp": (NP,PAGE,r), "krp": (NP,PAGE,dr),
    "len": (B,)} with a ``page_table`` (B, NPB): MLA pages the *latents*
    (the decoupled fetch reads r + dr bytes per token from the pool),
    gathers them contiguous, and up-projects exactly as the contiguous
    path does, so paged decode is bit-identical in both kernel modes
    whenever NPB*PAGE equals the contiguous s_max.
    S > 1 (or an explicit ``valid`` mask) with a cache is the chunked
    cache-fill path; see :func:`gqa_apply`."""
    b, s, d = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope, cfg.qk_rope_dim, cfg.v_hd
    r = cfg.kv_lora_rank
    dt = cfg.adtype

    q_nope, q_rope = _mla_q(cfg, p, x)
    q_rope = rope(q_rope.transpose(0, 2, 1, 3), positions[:, None, :],
                  cfg.rope_theta)                               # (B,H,S,dr)
    q_nope = q_nope.transpose(0, 2, 1, 3)                       # (B,H,S,dn)

    ckv = rmsnorm(x @ p["w_dkv"].astype(dt), p["kv_norm"], cfg.norm_eps)
    kr = rope((x @ p["w_kr"].astype(dt))[:, None, :, :],
              positions[:, None, :], cfg.rope_theta)            # (B,1,S,dr)

    paged = cache is not None and "ckvp" in cache
    chunked = cache is not None and not (s == 1 and valid is None)
    if paged:
        if page_table is None:
            raise ValueError("paged MLA cache requires a page_table")
        pos = cache["len"]
        if valid is None:
            valid = jnp.ones((b, s), bool)
        ckv_p = _pool_constraint(cfg, _scatter_vec_pages(
            cache["ckvp"], ckv, pos, valid, page_table))
        kr_p = _pool_constraint(cfg, _scatter_vec_pages(
            cache["krp"], kr[:, 0], pos, valid, page_table))
        lens = pos + valid.sum(-1).astype(pos.dtype)
        ckv_full = _gather_vec_pages(ckv_p, page_table)         # (B,Slog,r)
        kr_full = _gather_vec_pages(kr_p, page_table)[:, None]  # (B,1,Slog,dr)
        new_cache = {"ckvp": ckv_p, "krp": kr_p, "len": lens}
        s_kv = ckv_full.shape[1]
        chunked = True      # paged decode always takes the masked-chunk path
    elif cache is not None and not chunked:
        pos = cache["len"]
        ckv_c = _scatter_vec(cache["ckv"], ckv, pos)            # (B,Smax,r)
        kr_c = _scatter_vec(cache["kr"], kr[:, 0], pos)         # (B,Smax,dr)
        lens = pos + 1
        ckv_full, kr_full = ckv_c, kr_c[:, None]
        new_cache = {"ckv": ckv_c, "kr": kr_c, "len": lens}
        s_kv = ckv_c.shape[1]
    elif chunked:
        pos = cache["len"]
        if valid is None:
            valid = jnp.ones((b, s), bool)
        ckv_c = _scatter_vec_chunk(cache["ckv"], ckv, pos, valid)
        kr_c = _scatter_vec_chunk(cache["kr"], kr[:, 0], pos, valid)
        lens = pos + valid.sum(-1).astype(pos.dtype)
        ckv_full, kr_full = ckv_c, kr_c[:, None]
        new_cache = {"ckv": ckv_c, "kr": kr_c, "len": lens}
        s_kv = ckv_c.shape[1]
    else:
        ckv_full, kr_full = ckv, kr
        new_cache = None
        s_kv = s

    # up-project latents to per-head K/V (decode recomputes from latents —
    # the decoupled fetch reads only r + dr per token)
    k_nope = (ckv_full @ p["w_uk"].astype(dt)).reshape(b, s_kv, h, dn)
    v = (ckv_full @ p["w_uv"].astype(dt)).reshape(b, s_kv, h, dv)
    k_nope = k_nope.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_full, (b, h, s_kv, dr)).astype(dt)], -1)
    qk = jnp.concatenate([q_nope, q_rope], -1)                  # (B,H,S,dn+dr)

    if cache is None:
        out = _prefill_attention(cfg, qk, k, v_pad_to(v, k.shape[-1]),
                                 causal=causal, window=None)[..., :dv]
    elif chunked:
        qlens = pos[:, None] + jnp.arange(1, s + 1, dtype=pos.dtype)[None]
        if s == 1 and cfg.kernel_mode == "pallas":
            out = on_each_device(cfg, flash_decode, qk[:, :, 0, :], k,
                                 v_pad_to(v, k.shape[-1]), qlens[:, 0])
            out = out[..., :dv][:, :, None, :]
        else:
            out = decode_chunk_ref(qk, k, v_pad_to(v, k.shape[-1]),
                                   qlens)[..., :dv]            # (B,H,S,dv)
    else:
        qd = qk[:, :, 0, :]
        if cfg.kernel_mode == "pallas":
            out = flash_decode(qd, k, v_pad_to(v, k.shape[-1]),
                               new_cache["len"])[..., :dv]
        else:
            out = decode_ref(qd, k, v_pad_to(v, k.shape[-1]),
                             new_cache["len"])[..., :dv]
        out = out[:, :, None, :]

    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return out @ p["wo"].astype(dt), new_cache


def v_pad_to(v: jnp.ndarray, d: int) -> jnp.ndarray:
    """Pad value head dim to match k head dim for the fused kernel."""
    if v.shape[-1] == d:
        return v
    pad = [(0, 0)] * (v.ndim - 1) + [(0, d - v.shape[-1])]
    return jnp.pad(v, pad)


def _scatter_vec(cache: jnp.ndarray, new: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """cache (B, Smax, D); new (B, 1, D); pos (B,)."""
    smax = cache.shape[1]
    onehot = (jnp.arange(smax)[None, :] == pos[:, None])[..., None]
    return jnp.where(onehot, new.astype(cache.dtype), cache)


def _scatter_vec_chunk(cache: jnp.ndarray, new: jnp.ndarray,
                       pos: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """cache (B, Smax, D); new (B, C, D); pos (B,); valid (B, C)."""
    smax, c = cache.shape[1], new.shape[1]
    tgt = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]   # (B, C)
    onehot = ((tgt[:, :, None] == jnp.arange(smax)[None, None, :])
              & valid[:, :, None])                             # (B, C, Smax)
    upd = jnp.einsum("bcs,bcd->bsd", onehot.astype(cache.dtype),
                     new.astype(cache.dtype))
    return jnp.where(onehot.any(1)[..., None], upd, cache)
