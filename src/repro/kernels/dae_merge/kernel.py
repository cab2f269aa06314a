"""Decoupled merge of sorted runs (paper Listing 3, TPU-native form).

Hardware adaptation (docs/architecture.md §"TPU adaptation"): the FPGA
merge consumes one element per cycle with a data-dependent two-pointer
walk.  A TPU has no profitable serial path — instead we use the
*merge-path* decomposition:

  1. ops.py computes, for every output tile of size T, the (ia, ib)
     split such that the tile's output equals the first T elements of
     merge(a[ia:ia+T], b[ib:ib+T]).  These splits are the *Access*
     stream: they are computed *ahead* of the merge (a vectorized
     binary search over the diagonal), exactly like the paper's
     ``decouple_request`` loops run ahead over both runs.

  2. The kernel scalar-prefetches the split offsets; two
     :class:`~repro.kernels.ring.RingChannel`\\ s DMA the T-windows from
     HBM at *element* granularity (async copies with dynamic starts —
     irregular, decoupled loads) ``rif`` tiles ahead of the grid step
     that consumes them (:func:`~repro.kernels.ring.ring_step` spans the
     ring across grid steps), then each step merges its two windows with
     a branch-free bitonic merge network on the VPU and writes one dense
     output tile.

The request/response pairing is structural (the ring emitter issues one
request and one response per tile per run), and window padding with
+inf sentinels guarantees every tile consumes the exact number of
elements the splits promise (paper §5.1 correctness).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import (RingChannel, clamp_rif,
                                ring_scratch_shapes, ring_step)


def bitonic_merge_first_half(v: jnp.ndarray) -> jnp.ndarray:
    """Given v = concat(sorted_a, reversed(sorted_b)) of length 2T (a
    bitonic sequence), return the sorted first half (the T smallest)."""
    n = v.shape[0]
    d = n // 2
    while d >= 1:
        w = v.reshape(-1, 2, d)
        lo = jnp.minimum(w[:, 0, :], w[:, 1, :])
        hi = jnp.maximum(w[:, 0, :], w[:, 1, :])
        v = jnp.stack([lo, hi], axis=1).reshape(n)
        d //= 2
    return v[: n // 2]


def _merge_kernel(sa_ref, sb_ref, a_hbm, b_hbm, out_ref, wa, sem_a, wb, sem_b,
                  *, tile: int, n_tiles: int, rif: int):
    t = pl.program_id(0)
    ring_a = RingChannel(wa, sem_a, rif,
                         src=lambda k: a_hbm.at[pl.ds(sa_ref[k], tile)])
    ring_b = RingChannel(wb, sem_b, rif,
                         src=lambda k: b_hbm.at[pl.ds(sb_ref[k], tile)])

    def execute(win_a, win_b):
        v = jnp.concatenate([win_a, win_b[::-1]])
        out_ref[...] = bitonic_merge_first_half(v)

    ring_step([ring_a, ring_b], t, n_tiles, execute)


def merge_tiles(a_pad: jax.Array, b_pad: jax.Array, starts_a: jax.Array,
                starts_b: jax.Array, n_out: int, *, tile: int, rif: int = 2,
                interpret: bool) -> jax.Array:
    """a_pad/b_pad are the runs padded with +inf sentinels so any
    (start, start+tile) window is in bounds; starts_* (n_tiles,) are the
    merge-path splits; output is n_out = n_tiles * tile elements.
    ``rif`` window pairs stream ahead of the consuming grid step."""
    n_tiles = starts_a.shape[0]
    rif = clamp_rif(rif, n_tiles)
    kernel = functools.partial(_merge_kernel, tile=tile, n_tiles=n_tiles,
                               rif=rif)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile,), lambda t, sa, sb: (t,)),
            scratch_shapes=[
                *ring_scratch_shapes(rif, (tile,), a_pad.dtype),
                *ring_scratch_shapes(rif, (tile,), b_pad.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out,), a_pad.dtype),
        interpret=interpret,
    )(starts_a, starts_b, a_pad, b_pad)
