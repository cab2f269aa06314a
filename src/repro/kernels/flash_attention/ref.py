"""Pure-jnp oracle for flash attention (prefill + decode)."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """q (B,H,S,D); k,v (B,KVH,S,D); GQA by head repetition."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    rows = jnp.arange(sq)[:, None]
    cols = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols >= rows - window + 1
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jnp.exp(logits - logits.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def attention_chunked(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      scale: Optional[float] = None,
                      chunk: int = 1024, unroll: bool = False) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure XLA: scans KV chunks
    so no (S, S) tensor is ever materialized.  This is the beyond-paper
    §Perf lever for the dry-run (the Pallas flash kernel implements the
    same schedule with explicit DMA decoupling on real TPU).

    ``unroll=True`` replaces the lax.scan with a python loop so the
    dry-run cost probes count every chunk (XLA counts scan bodies once).
    """
    import jax

    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32) * scale
    chunk = min(chunk, sk)
    while sk % chunk:
        chunk -= 1
    nk = sk // chunk
    kc = k.reshape(b, h, nk, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, nk, chunk, d).transpose(2, 0, 1, 3, 4)
    rows = jnp.arange(sq)[:, None]

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        ki, kblk, vblk = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        cols = ki * chunk + jnp.arange(chunk)[None, :]
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols >= rows - window + 1
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, d), jnp.float32))
    if unroll:
        carry = init
        for ki in range(nk):
            carry, _ = step(carry, (jnp.asarray(ki), kc[ki], vc[ki]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, init,
                                      (jnp.arange(nk), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def attention_banded(q, k, v, *, window: int, causal: bool = True,
                     scale: Optional[float] = None, chunk: int = 1024,
                     unroll: bool = False) -> jnp.ndarray:
    """Sliding-window attention that only TOUCHES the band.

    For each q chunk [iC, iC+C), the causal window [row-W+1, row] lies in
    the fixed-width KV slice [iC+C-1-W+1-(C-1), iC+C) -> width W+C.  Per-
    chunk cost is C x (W+C): total S(W+C) instead of S^2 — both FLOPs and
    HBM bytes drop by ~S/(W+C).  This is the banded §Perf lever for the
    long-context window archs (hymba)."""
    import jax

    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    scale = scale if scale is not None else d ** -0.5
    chunk = min(chunk, sq)
    while sq % chunk:
        chunk -= 1
    nq = sq // chunk
    band = window + chunk          # fixed slice width
    # left-pad K/V so every band slice is in bounds
    pad = ((0, 0), (0, 0), (band - chunk, 0), (0, 0))
    kp = jnp.pad(k, pad)
    vp = jnp.pad(v, pad)
    qc = q.reshape(b, h, nq, chunk, d)

    def one_chunk(i):
        qi = (qc[:, :, i] if isinstance(i, int)
              else jax.lax.dynamic_index_in_dim(qc, i, 2, keepdims=False))
        start = (i * chunk if isinstance(i, int)
                 else i * chunk)           # padded start of the band
        kb = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32) * scale,
                       kb.astype(jnp.float32))
        rows = i * chunk + jnp.arange(chunk)[:, None]          # global row
        cols = (start - (band - chunk)) + jnp.arange(band)[None, :]
        mask = cols >= 0
        if causal:
            mask &= cols <= rows
        mask &= cols >= rows - window + 1
        s = jnp.where(mask[None, None], s, NEG_INF)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return out / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)

    if unroll:
        outs = [one_chunk(i) for i in range(nq)]
        out = jnp.stack(outs, axis=2)
    else:
        out = jax.lax.map(lambda i: one_chunk(i), jnp.arange(nq))
        out = jnp.moveaxis(out, 0, 2)
    return out.reshape(b, h, sq, d).astype(q.dtype)


def decode_chunk_ref(q, k_cache, v_cache, lengths, *,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Multi-query decode against a KV cache: the chunked-prefill oracle.

    q (B,H,C,D) — C new queries per batch row; caches (B,KVH,S,D);
    lengths (B,C) — per-query visible prefix (query i of row b attends
    cache positions < lengths[b, i]).

    Deliberately a sequential ``lax.map`` of :func:`decode_ref` over the
    C queries rather than one (C, S) GEMM: XLA's accumulation order
    depends on the GEMM shape, and the serving parity tests pin chunked
    prefill BIT-IDENTICAL to a run of single-token decode steps.  FLOPs
    are identical either way; only the K/V re-reads differ, which the
    ref oracle does not model.

    This is the ``ref``-mode oracle, kept for that bit-identity (ROADMAP
    D2), and no longer on the ``pallas`` serving path: there a chunk
    attends through ``models.attention.grouped_chunk_attention``, one
    grouped MXU GEMM per KV head, equal to this within float32 rounding.
    """
    import jax

    out = jax.lax.map(
        lambda ql: decode_ref(ql[0], k_cache, v_cache, ql[1], scale=scale),
        (q.transpose(2, 0, 1, 3), lengths.T))                  # (C,B,H,D)
    return out.transpose(1, 2, 0, 3)                           # (B,H,C,D)


def decode_ref(q, k_cache, v_cache, lengths, *,
               scale: Optional[float] = None) -> jnp.ndarray:
    """q (B,H,D); caches (B,KVH,S,D); lengths (B,) valid prefix lengths."""
    b, h, d = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    kc = jnp.repeat(k_cache, g, axis=1) if g > 1 else k_cache
    vc = jnp.repeat(v_cache, g, axis=1) if g > 1 else v_cache
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, :] < lengths[:, None]          # (B, S)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    p = jnp.exp(logits - logits.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhk,bhkd->bhd", p, vc.astype(jnp.float32)).astype(q.dtype)
