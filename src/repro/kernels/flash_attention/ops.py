"""Jit'd public wrappers for flash attention."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import (note_knobs, resolve_interpret, ring_rif,
                                  round_up, tuned_knobs)
from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention.ref import attention_ref, decode_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret", "method"))
def _flash_impl(q, k, v, *, causal, window, bq, bk, interpret, method):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = d ** -0.5
    if method == "ref":
        return attention_ref(q, k, v, causal=causal, window=window)
    sqp, skp = round_up(sq, bq), round_up(sk, bk)
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        pad = ((0, 0), (0, 0), (0, skp - sk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    out = _k.flash(q, k, v, causal=causal, window=window, scale=scale,
                   s_real=sk, bq=bq, bk=bk, interpret=interpret)
    return out[:, :, :sq, :]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None,
                    method: str = "pallas",
                    interpret: Optional[bool] = None) -> jax.Array:
    """q (B,H,S,D); k,v (B,KVH,S,D) with H % KVH == 0 (GQA).

    ``bq``/``bk`` left ``None`` resolve via the tune cache (128 default).
    """
    interp = resolve_interpret(interpret)
    if bq is None or bk is None:
        knobs = tuned_knobs("flash_attention",
                            (q.shape[2], k.shape[2], q.shape[3]), q.dtype,
                            interp, bq=(bq, 128), bk=(bk, 128))
        bq, bk = knobs["bq"], knobs["bk"]
    note_knobs("flash_attention", (q.shape[2], k.shape[2], q.shape[3]),
               bq=bq, bk=bk)
    return _flash_impl(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                       interpret=interp, method=method)


@functools.partial(jax.jit, static_argnames=("bk", "rif", "interpret",
                                              "method"))
def _decode_impl(q, k_cache, v_cache, lengths, *, bk, rif, interpret, method):
    b, h, d = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = d ** -0.5
    if method == "ref":
        return decode_ref(q, k_cache, v_cache, lengths)
    sp = round_up(s, bk)
    if sp != s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    qg = q.reshape(b, kvh, g, d)
    out = _k.flash_decode(qg, k_cache, v_cache, lengths.astype(jnp.int32),
                          scale=scale, bk=bk, rif=rif, interpret=interpret)
    return out.reshape(b, h, d)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, bk: Optional[int] = None,
                 rif: Optional[int] = None, method: str = "pallas",
                 interpret: Optional[bool] = None) -> jax.Array:
    """One-token decode: q (B,H,D) against caches (B,KVH,S,D).

    ``bk``/``rif`` left ``None`` resolve explicit → tune cache →
    analytic (bk 128; ``plan_rif`` over one (bk, d) block's byte
    size)."""
    interp = resolve_interpret(interpret)
    if bk is None or rif is None:
        knobs = tuned_knobs("flash_decode", (k_cache.shape[2], q.shape[2]),
                            q.dtype, interp, bk=(bk, 128), rif=(rif, None))
        bk, rif = knobs["bk"], knobs["rif"]
        rif = ring_rif(rif, bk * q.shape[2] * q.dtype.itemsize)
    note_knobs("flash_decode", (k_cache.shape[2], q.shape[2]), bk=bk,
               rif=rif)
    return _decode_impl(q, k_cache, v_cache, lengths, bk=bk, rif=rif,
                        interpret=interp, method=method)


@functools.partial(jax.jit, static_argnames=("rif", "interpret", "method"))
def _decode_paged_impl(q, k_pages, v_pages, page_table, lengths, *,
                       rif, interpret, method):
    b, h, d = q.shape
    kvh = k_pages.shape[1]
    g = h // kvh
    scale = d ** -0.5
    if method == "ref":
        # reconstruct contiguous caches from pages for the oracle
        np_, _, page, _ = k_pages.shape
        kc = jnp.take(k_pages, page_table, axis=0)   # (B, NPB, KVH, PAGE, D)
        kc = kc.transpose(0, 2, 1, 3, 4).reshape(b, kvh, -1, d)
        vc = jnp.take(v_pages, page_table, axis=0)
        vc = vc.transpose(0, 2, 1, 3, 4).reshape(b, kvh, -1, d)
        return decode_ref(q, kc, vc, lengths)
    qg = q.reshape(b, kvh, g, d)
    out = _k.flash_decode_paged(qg, k_pages, v_pages,
                                page_table.astype(jnp.int32),
                                lengths.astype(jnp.int32), scale=scale,
                                rif=rif, interpret=interpret)
    return out.reshape(b, h, d)


def flash_decode_paged(q, k_pages, v_pages, page_table, lengths, *,
                       rif: Optional[int] = None, method: str = "pallas",
                       interpret: Optional[bool] = None) -> jax.Array:
    """Paged decode: pages (NP,KVH,PAGE,D), page_table (B, S/PAGE) int32.

    ``rif=None`` resolves the page-ring depth via the tune cache, then
    ``plan_rif`` over one page's byte size."""
    interp = resolve_interpret(interpret)
    if rif is None:
        rif = tuned_knobs("flash_decode_paged",
                          (k_pages.shape[2], q.shape[2]), q.dtype, interp,
                          rif=(None, None))["rif"]
        rif = ring_rif(rif, k_pages.shape[2] * q.shape[2]
                       * q.dtype.itemsize)
    note_knobs("flash_decode_paged", (k_pages.shape[2], q.shape[2]),
               rif=rif)
    return _decode_paged_impl(q, k_pages, v_pages, page_table, lengths,
                              rif=rif, interpret=interp, method=method)
