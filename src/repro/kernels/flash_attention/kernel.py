"""Block-streamed flash attention — decoupled KV fetch on TPU.

The DAE view (docs/architecture.md §"TPU adaptation"): the KV block
stream is the *Access* side — the request for block k+rif is issued
while the MXU consumes block k (decoupled request/response with the
buffer ring as the RIF window).  Online softmax is the Execute loop's
bounded state, the same role as Listing 4's ``state`` stream.

Variants:
  * ``flash`` — prefill: causal / sliding-window, GQA via head mapping.
    The KV stream is regular, so the Pallas pipeline's own BlockSpec
    double-buffering is the ring (RIF = 2).
  * ``flash_decode`` — one new token against a KV cache; the q-head
    group of a KV head is folded into MXU rows.  The K/V block streams
    are two explicit :class:`~repro.kernels.ring.RingChannel`\\ s of
    depth ``rif`` spanning the ``nk`` grid dimension
    (:func:`~repro.kernels.ring.ring_step`).
  * paged decode — same rings, but the scalar-prefetched page table
    supplies the block addresses: an irregular, data-dependent block
    gather (exactly ``dae_gather`` fused into attention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import (RingChannel, clamp_rif,
                                ring_scratch_shapes, ring_step)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool,
                  window: Optional[int], s_real: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32)              # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)              # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols < s_real
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols >= rows - window + 1
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def flash(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
          window: Optional[int], scale: float, s_real: int, bq: int, bk: int,
          interpret: bool) -> jax.Array:
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    nq, nk = sq // bq, sk // bk
    grid = (b, h, nq, nk)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                               causal=causal, window=window, s_real=s_real)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Decode (contiguous and paged KV)
# ---------------------------------------------------------------------------


def _decode_step(len_ref, q_ref, o_ref, acc, m_s, l_s, k_blk, v_blk, *,
                 bk: int, nk: int, scale: float):
    """Online-softmax update for one (BK, D) K/V block pair — the Execute
    side shared by the contiguous and paged decode kernels."""
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
    k = k_blk.astype(jnp.float32)                    # (BK, D)
    v = v_blk.astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < len_ref[b], s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, acc, m_s, l_s,
                   kscr, ksem, vscr, vsem, *, bk: int, nk: int, rif: int,
                   scale: float):
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    ring_k = RingChannel(kscr, ksem, rif,
                         src=lambda k: k_hbm.at[b, h, pl.ds(k * bk, bk), :])
    ring_v = RingChannel(vscr, vsem, rif,
                         src=lambda k: v_hbm.at[b, h, pl.ds(k * bk, bk), :])

    def execute(k_blk, v_blk):
        _decode_step(len_ref, q_ref, o_ref, acc, m_s, l_s, k_blk, v_blk,
                     bk=bk, nk=nk, scale=scale)

    ring_step([ring_k, ring_v], ki, nk, execute)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, scale: float, bk: int, rif: int = 2,
                 interpret: bool) -> jax.Array:
    """q (B, KVH, G, D); caches (B, KVH, S, D); lengths (B,) int32.
    ``rif`` K/V block pairs stream ahead of the MXU consume."""
    b, kvh, g, d = q.shape
    s = k_cache.shape[2]
    nk = s // bk
    rif = clamp_rif(rif, nk)
    grid = (b, kvh, nk)

    kernel = functools.partial(_decode_kernel, bk=bk, nk=nk, rif=rif,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b_, h_, k_, L: (b_, h_, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, h_, k_, L: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                *ring_scratch_shapes(rif, (bk, d), k_cache.dtype),
                *ring_scratch_shapes(rif, (bk, d), v_cache.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref,
                         acc, m_s, l_s, kscr, ksem, vscr, vsem, *, bk: int,
                         nk: int, rif: int, scale: float):
    # identical math to _decode_kernel; the scalar-prefetched page table
    # supplies the ring's addresses (the decoupled request stream)
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    ring_k = RingChannel(kscr, ksem, rif,
                         src=lambda k: k_hbm.at[pt_ref[b, k], h])
    ring_v = RingChannel(vscr, vsem, rif,
                         src=lambda k: v_hbm.at[pt_ref[b, k], h])

    def execute(k_blk, v_blk):
        _decode_step(len_ref, q_ref, o_ref, acc, m_s, l_s, k_blk, v_blk,
                     bk=bk, nk=nk, scale=scale)

    ring_step([ring_k, ring_v], ki, nk, execute)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, lengths: jax.Array, *,
                       scale: float, rif: int = 2,
                       interpret: bool) -> jax.Array:
    """q (B, KVH, G, D); pages (NP, KVH, PAGE, D); page_table (B, S/PAGE).

    The page table is the decoupled request stream: the K/V rings consume
    it ahead of the MXU — a data-dependent block gather fused into
    attention (dae_gather's addressing inside flash).
    """
    b, kvh, g, d = q.shape
    n_pages, _, page, _ = k_pages.shape
    npb = page_table.shape[1]
    rif = clamp_rif(rif, npb)
    grid = (b, kvh, npb)

    kernel = functools.partial(_paged_decode_kernel, bk=page, nk=npb,
                               rif=rif, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b_, h_, k_, L, pt: (b_, h_, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, h_, k_, L, pt: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                *ring_scratch_shapes(rif, (page, d), k_pages.dtype),
                *ring_scratch_shapes(rif, (page, d), v_pages.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(lengths, page_table, q, k_pages, v_pages)
