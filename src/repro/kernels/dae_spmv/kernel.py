"""Decoupled block-sparse SPMV (paper Listing 2, TPU-native form).

Hardware adaptation (docs/architecture.md §"TPU adaptation"): the FPGA
version streams scalar ``val``/``cols`` words; a TPU moves 512-byte-
granule DMAs and multiplies on a 128x128 MXU, so the unit of irregular
access is a *block*: the matrix is BSR (blocks of (BM, BK)), the dense
vector is tiled in BK chunks, and the decoupled load is the vec-tile
fetch whose address comes from the scalar-prefetched ``col_ids`` stream.
That fetch is emitted through :mod:`repro.kernels.ring`: a
:class:`~repro.kernels.ring.RingChannel` of depth ``rif`` runs the
Access stream ``rif`` grid steps ahead of the MXU consume
(:func:`~repro.kernels.ring.ring_step` spans the ring across grid
steps) — exactly the paper's Access loop running ahead of Execute.

The ``row_ids`` stream (CSR order, monotone) drives *output* block
revisiting: consecutive grid steps with the same row accumulate in VMEM,
and the first step of each row zero-initializes — removing the false
dependency of products on row-pointer loads, as in Listing 2 (right).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import (RingChannel, clamp_rif,
                                ring_scratch_shapes, ring_step)


def _spmv_kernel(row_ref, col_ref, val_ref, vec_hbm, out_ref, vscr, vsem, *,
                 nb: int, rif: int):
    i = pl.program_id(0)
    ring = RingChannel(vscr, vsem, rif,
                       src=lambda k: vec_hbm.at[pl.ds(col_ref[k], 1), :])

    def execute(vec_tile):
        is_first = jnp.logical_or(i == 0,
                                  row_ref[i] != row_ref[jnp.maximum(i - 1, 0)])

        @pl.when(is_first)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # (1, BK) @ (BM, BK)^T -> (1, BM) on the MXU
        prod = jax.lax.dot_general(
            vec_tile, val_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out_ref[...] += prod.astype(out_ref.dtype)

    ring_step([ring], i, nb, execute)


def bsr_spmv(val_blocks: jax.Array, row_ids: jax.Array, col_ids: jax.Array,
             vec_tiles: jax.Array, nrows_blocks: int, *, rif: int = 2,
             interpret: bool) -> jax.Array:
    """val_blocks (NB, BM, BK); row_ids/col_ids (NB,) with row_ids sorted
    ascending and every row block present at least once (ops.py pads empty
    rows with zero blocks); vec_tiles (KB, BK) -> out (nrows_blocks, BM).
    ``rif`` vec-tile fetches stream ahead of the consuming grid step."""
    nb, bm, bk = val_blocks.shape
    rif = clamp_rif(rif, nb)
    grid = (nb,)
    kernel = functools.partial(_spmv_kernel, nb=nb, rif=rif)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda i, r, c: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, bm), lambda i, r, c: (r[i], 0)),
            scratch_shapes=[
                *ring_scratch_shapes(rif, (1, bk), vec_tiles.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nrows_blocks, bm), val_blocks.dtype),
        interpret=interpret,
    )(row_ids, col_ids, val_blocks.reshape(nb, 1 * bm, bk), vec_tiles)
