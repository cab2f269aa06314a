"""Jit'd public wrapper for the decoupled gather kernel.

Handles shape padding, method dispatch, and the ref fallback used by the
dry-run path (``method='ref'``) where the compiled HLO must reflect the
XLA gather the roofline accounts for.

Knobs left at ``None`` resolve through ``repro.tune``: a cached tuned
config for this (shape, dtype, backend) wins, otherwise the analytic
``plan_rif`` latency×bandwidth plan sizes the ring.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import (note_knobs, resolve_interpret, ring_rif,
                                  round_up, tuned_knobs)
from repro.kernels.dae_gather import kernel as _k
from repro.kernels.dae_gather.ref import gather_ref


@functools.partial(
    jax.jit,
    static_argnames=("method", "block_d", "chunk", "rif", "interpret"))
def _dae_gather_impl(table, idx, *, method, block_d, chunk, rif, interpret):
    n, d = table.shape
    m = idx.shape[0]
    idx = idx.astype(jnp.int32)

    if method == "ref":
        return gather_ref(table, idx)

    # pad rows to whole tiles and the feature dim to the lane
    # granularity the kernels require (a no-op for aligned tables)
    dp = round_up(d, 128)
    np_ = round_up(n, _k.ROW_TILE)
    if (np_, dp) != (n, d):
        table = jnp.pad(table, ((0, np_ - n), (0, dp - d)))

    if method == "pipelined":
        bd = block_d or min(dp, 512)
        bd = dp // max(1, dp // bd)  # ensure divisibility
        while dp % bd:
            bd -= 1
        out = _k.gather_pipelined(table, idx, block_d=bd, interpret=interpret)
    elif method == "rif":
        c = min(chunk, m) or 1
        mp = round_up(m, c)
        if mp != m:
            idx = jnp.pad(idx, (0, mp - m))
        out = _k.gather_rif(table, idx, chunk=c, rif=min(rif, c),
                            interpret=interpret)
        out = out[:m]
    else:
        raise ValueError(f"unknown method {method!r}")

    return out[:, :d]


def dae_gather(
    table: jax.Array,
    idx: jax.Array,
    *,
    method: Optional[str] = None,
    block_d: Optional[int] = None,
    chunk: Optional[int] = None,
    rif: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decoupled gather of ``table`` (N, D) rows at ``idx`` (M,) -> (M, D).

    method='pipelined': scalar-prefetch indexed BlockSpec (RIF = pipeline
    double-buffering); method='rif': explicit multi-buffer DMA ring with
    ``rif`` requests in flight; method='ref': jnp oracle (XLA gather).

    Knobs left ``None`` resolve via the tune cache, then ``plan_rif``.
    """
    interp = resolve_interpret(interpret)
    n, d = table.shape
    if method is None or block_d is None or chunk is None or rif is None:
        knobs = tuned_knobs("dae_gather", (n, d, idx.shape[0]), table.dtype,
                            interp, method=(method, "pipelined"),
                            block_d=(block_d, None), chunk=(chunk, 64),
                            rif=(rif, None))
        method, block_d, chunk = knobs["method"], knobs["block_d"], \
            knobs["chunk"]
        dp = round_up(max(d, 1), 128)
        rif = ring_rif(knobs["rif"], chunk * dp * table.dtype.itemsize)
    note_knobs("dae_gather", (n, d, idx.shape[0]), method=method,
               block_d=block_d, chunk=chunk, rif=rif)
    return _dae_gather_impl(table, idx, method=method, block_d=block_d,
                            chunk=chunk, rif=rif, interpret=interp)
