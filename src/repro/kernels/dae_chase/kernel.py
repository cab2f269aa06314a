"""Decoupled pointer-chase kernels (paper §4.2, Listings 4/5) on TPU.

These are the dependent-load workloads where the paper's 10–79×
speedups live; both kernels are emitted through
:mod:`repro.kernels.ring`, so the request/response pairing and the
prologue/steady-state/drain loop structure are the shared emitter's,
not hand-rolled here.

* ``searchsorted_blocks`` — block binary search.  ops.py resolves each
  key to a table *block* id with a VMEM-resident summary search (the top
  of the B-tree); the kernel then keeps ``rif`` independent block probes
  in flight per grid step (the block-id stream is scalar-prefetched —
  the Access loop's address stream) and resolves log2(block) levels of
  the search per response with one vectorized compare-reduce.

* ``hash_probe`` — lock-step chain walk over a separate-chaining hash
  table.  Each grid step owns ``chunk`` chains whose current positions
  live in SMEM; every level runs a full :func:`access_execute` pass over
  the chunk, so ``rif`` *independent dependent-load chains* stay in
  flight while each individual chain waits on its own pointer — exactly
  Listing 5's fixed-length lock-step variant, including the redundant
  tail re-loads for resolved chains (masking instead of
  conditional-issue circuitry).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import RingChannel, access_execute, \
    clamp_rif, ring_scratch_shapes

# packed hash-table entry rows are padded to one DMA-aligned lane group
ENTRY_LANES = 128


# ---------------------------------------------------------------------------
# Block binary search
# ---------------------------------------------------------------------------


def _searchsorted_kernel(blk_ref, keys_ref, tiles_hbm, out_ref, scratch,
                         sems, *, chunk: int, rif: int, block: int, n: int):
    """``chunk`` key probes per grid step, ``rif`` block fetches in
    flight.  Each response resolves a whole block: the 'right' insertion
    point is blk*block + |{x in block : x <= key}| (padding sentinels are
    +inf/intmax, so they never count below a real key)."""
    c = pl.program_id(0)
    base = c * chunk

    ring = RingChannel(
        scratch, sems, rif,
        src=lambda k: tiles_hbm.at[pl.ds(blk_ref[base + k], 1), :])

    def execute(k, row):
        key = keys_ref[pl.ds(k, 1)]                        # (1,)
        within = jnp.sum((row <= key[0]).astype(jnp.int32))
        idx = blk_ref[base + k] * block + within
        out_ref[pl.ds(k, 1)] = jnp.minimum(idx, n).astype(jnp.int32)[None]

    access_execute([ring], chunk, execute)


def searchsorted_blocks(tiles: jax.Array, blk: jax.Array, keys: jax.Array,
                        n: int, *, chunk: int, rif: int,
                        interpret: bool) -> jax.Array:
    """tiles (NB, block) is the padded sorted table; blk (M,) int32 maps
    each key to the block holding its insertion point (ops.py's summary
    search); keys (M,) padded to a multiple of ``chunk``.  Returns (M,)
    int32 'right' insertion points clipped to ``n``."""
    m = keys.shape[0]
    nb, block = tiles.shape
    assert m % chunk == 0, (m, chunk)
    rif = clamp_rif(rif, chunk)
    grid = (m // chunk,)

    kernel = functools.partial(_searchsorted_kernel, chunk=chunk, rif=rif,
                               block=block, n=n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((chunk,), lambda c, b_: (c,)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((chunk,), lambda c, b_: (c,)),
            scratch_shapes=[
                *ring_scratch_shapes(rif, (1, block), tiles.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.int32),
        interpret=interpret,
    )(blk, keys, tiles)


# ---------------------------------------------------------------------------
# Lock-step hash-chain walk
# ---------------------------------------------------------------------------


def _hash_probe_kernel(heads_ref, keys_ref, packed_hbm, out_ref, idx_s,
                       found_v, val_v, scratch, sems, *, chunk: int,
                       rif: int, max_steps: int, n: int):
    c = pl.program_id(0)
    base = c * chunk

    # Only the chain cursor needs per-scalar SMEM (the ring's src reads
    # it back one scalar at a time); found/val state lives as VMEM
    # vectors so init and emit are single vector ops, not chunk-long
    # scalar loops.
    def init(k, _):
        idx_s[k] = heads_ref[base + k]
        return 0

    jax.lax.fori_loop(0, chunk, init, 0)
    found_v[...] = jnp.zeros((1, chunk), jnp.int32)
    val_v[...] = jnp.full((1, chunk), -1, jnp.int32)

    # the Access stream reads the per-chain cursor back out of SMEM: a
    # resolved or dead chain keeps re-requesting a clipped address
    # (Listing 5's redundant loads) so the request/response pairing
    # stays structural across the whole level
    ring = RingChannel(
        scratch, sems, rif,
        src=lambda k: packed_hbm.at[
            pl.ds(jnp.clip(idx_s[k], 0, n - 1), 1), :])

    def execute(k, ent):
        ek, ev, nxt = ent[0, 0], ent[0, 1], ent[0, 2]
        cur = idx_s[k]
        found_k = found_v[pl.ds(0, 1), pl.ds(k, 1)][0, 0]
        alive = (cur >= 0) & (found_k == 0)
        hit = alive & (ek == keys_ref[base + k])
        val_k = val_v[pl.ds(0, 1), pl.ds(k, 1)][0, 0]
        val_v[pl.ds(0, 1), pl.ds(k, 1)] = jnp.where(hit, ev, val_k)[None, None]
        found_v[pl.ds(0, 1), pl.ds(k, 1)] = jnp.where(hit, 1,
                                                      found_k)[None, None]
        idx_s[k] = jnp.where(alive & ~hit, nxt, cur)

    def level(_, carry):
        # one full prologue/steady-state/drain pass over the chunk per
        # chain level: rif chains in flight, every chain one step deeper
        access_execute([ring], chunk, execute)
        return carry

    jax.lax.fori_loop(0, max_steps, level, 0)

    out_ref[...] = jnp.where(found_v[0, :] == 1, val_v[0, :], -1)


def hash_probe(packed: jax.Array, heads: jax.Array, keys: jax.Array, *,
               chunk: int, rif: int, max_steps: int,
               interpret: bool) -> jax.Array:
    """packed (N, ENTRY_LANES) int32 rows [key, val, next, 0...]; heads /
    keys (M,) int32 padded to a multiple of ``chunk``.  Returns (M,)
    int32 lookup values (-1 when not found within ``max_steps``)."""
    m = heads.shape[0]
    n = packed.shape[0]
    assert m % chunk == 0, (m, chunk)
    rif = clamp_rif(rif, chunk)
    grid = (m // chunk,)

    kernel = functools.partial(_hash_probe_kernel, chunk=chunk, rif=rif,
                               max_steps=max_steps, n=n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk,), lambda c, h_, k_: (c,)),
            scratch_shapes=[
                pltpu.SMEM((chunk,), jnp.int32),
                pltpu.VMEM((1, chunk), jnp.int32),
                pltpu.VMEM((1, chunk), jnp.int32),
                *ring_scratch_shapes(rif, (1, packed.shape[1]),
                                     packed.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.int32),
        interpret=interpret,
    )(heads, keys, packed)
