"""Decoupled row gather — the TPU realization of the paper's decoupled load.

Two variants, mirroring the two decoupling mechanisms described in
docs/architecture.md §"TPU adaptation":

* ``gather_pipelined`` — the *scalar-prefetch* form.  The index vector is
  prefetched to SMEM (`PrefetchScalarGridSpec`), so the Pallas pipeline's
  DMA-issue stage knows the HBM address of step *i*'s row several grid
  steps before the compute stage consumes it.  This is
  ``decouple_request`` (issue) / ``decouple_response`` (kernel body)
  with the buffer ring as the RIF window — Pallas double-buffers, so
  RIF=2 blocks in flight.

* ``gather_rif`` — the *manual multi-buffer DMA* form (Listing 4's RIF
  generalization), emitted through :mod:`repro.kernels.ring`: a
  :class:`~repro.kernels.ring.RingChannel` keeps ``rif`` async HBM→VMEM
  copies in flight, and :func:`~repro.kernels.ring.access_execute`
  generates the prologue/steady-state/drain structure.  Every request is
  matched by exactly one wait (the paper's §5.1 conservation rule,
  structurally enforced), and capacity is the ring depth — deadlock-free
  by construction.

Both fetch the aligned ``ROW_TILE``-row tile that holds the wanted row,
not the row alone: Mosaic refuses a one-row slice of a VMEM buffer at a
row offset it cannot prove tile-aligned, and row ``idx[i]`` sits at
offset ``idx[i] % ROW_TILE`` of its tile.  :func:`_select_row` picks
it out with a masked sum over the tile's rows, and each output row
is written as its own ``(1, D)`` slab of a ``(M, 1, D)`` output, whose
leading dim takes any dynamic index.  The table keeps its ``(N, D)``
layout (``N`` a multiple of ``ROW_TILE``; ``ops.py`` pads), so no call
relayouts it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import RingChannel, access_execute, \
    ring_scratch_shapes


# ---------------------------------------------------------------------------
# Variant 1: scalar-prefetch pipelined gather
# ---------------------------------------------------------------------------


ROW_TILE = 8   # rows of one (8, 128) HBM/VMEM tile: the DMA/load unit


def _select_row(tile: jax.Array, sub) -> jax.Array:
    """Row ``sub`` (traced) of a ``(ROW_TILE, W)`` tile as ``(1, W)``,
    bit-exact: the tile's bits as int32, every other row zeroed, summed
    over the rows.  Narrow dtypes widen first to a 32-bit type that
    holds each of their values exactly."""
    wide = tile
    if tile.dtype.itemsize < 4:
        wide = tile.astype(jnp.float32 if jnp.issubdtype(
            tile.dtype, jnp.floating) else jnp.int32)
    bits = jax.lax.bitcast_convert_type(wide, jnp.int32)
    rows = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    picked = jnp.where(rows == sub, bits, 0).sum(axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(picked, wide.dtype).astype(
        tile.dtype)


def _gather_block_kernel(idx_ref, table_ref, out_ref):
    # The response side: the tile holding row idx[i] has already been
    # DMA'd into VMEM by the pipeline; consuming it picks the row out.
    sub = idx_ref[pl.program_id(0)] % ROW_TILE
    out_ref[...] = _select_row(table_ref[...], sub)


def gather_pipelined(table: jax.Array, idx: jax.Array, *, block_d: int,
                     interpret: bool) -> jax.Array:
    """Gather ``table[idx]`` with one (ROW_TILE, block_d) tile fetched
    per grid step.  ``table`` is (N, D) with N % ROW_TILE == 0 and
    D % block_d == 0."""
    m = idx.shape[0]
    n, d = table.shape
    assert d % block_d == 0, (d, block_d)
    assert n % ROW_TILE == 0, (n, ROW_TILE)
    grid = (m, d // block_d)

    out = pl.pallas_call(
        _gather_block_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((ROW_TILE, block_d),
                             lambda i, j, idx_ref: (idx_ref[i] // ROW_TILE,
                                                    j)),
            ],
            out_specs=pl.BlockSpec((None, 1, block_d),
                                   lambda i, j, idx_ref: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, d), table.dtype),
        interpret=interpret,
    )(idx, table)
    return out.reshape(m, d)


# ---------------------------------------------------------------------------
# Variant 2: manual multi-buffer DMA gather (explicit RIF)
# ---------------------------------------------------------------------------


def _gather_rif_kernel(idx_ref, table_hbm, out_ref, scratch, sems, *,
                       chunk: int, rif: int):
    """Process ``chunk`` rows per grid step with ``rif`` copies in flight.

    ring.request = decouple_request (async start on slot k % rif)
    ring.response + copy-out = decouple_response
    """
    c = pl.program_id(0)
    base = c * chunk

    def tile_start(k):
        return pl.multiple_of(idx_ref[base + k] // ROW_TILE * ROW_TILE,
                              ROW_TILE)

    ring = RingChannel(
        scratch, sems, rif,
        src=lambda k: table_hbm.at[pl.ds(tile_start(k), ROW_TILE), :])

    def execute(k, tile):
        out_ref[k] = _select_row(tile, idx_ref[base + k] % ROW_TILE)

    access_execute([ring], chunk, execute)


def gather_rif(table: jax.Array, idx: jax.Array, *, chunk: int, rif: int,
               interpret: bool) -> jax.Array:
    """``table`` (N, D) with N % ROW_TILE == 0; ``idx`` (M,) with
    M % chunk == 0."""
    m = idx.shape[0]
    n, d = table.shape
    assert m % chunk == 0
    assert n % ROW_TILE == 0, (n, ROW_TILE)
    grid = (m // chunk,)

    kernel = functools.partial(_gather_rif_kernel, chunk=chunk, rif=rif)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk, 1, d), lambda c, idx_ref: (c, 0, 0)),
            scratch_shapes=[*ring_scratch_shapes(rif, (ROW_TILE, d),
                                                 table.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, d), table.dtype),
        interpret=interpret,
    )(idx, table)
    return out.reshape(m, d)
