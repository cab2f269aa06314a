"""Grouped expert matmul — decoupled SPMV generalized to MoE (paper §4.1).

After top-k routing, the token→expert map is CSR-shaped (group offsets =
``rows``).  The false dependency the paper removes for SPMV — products
gated by row-pointer loads — appears here as expert GEMMs gated by the
routing result.  Decoupling: ops.py sorts tokens by expert and emits a
``block_expert`` stream (one expert id per token block), and the expert
*weight* tiles stream through the shared ring emitter
(:mod:`repro.kernels.ring`): a ``rif``-deep
:class:`~repro.kernels.ring.RingChannel` issues the HBM→VMEM copy for
tile ``b + rif`` — an irregular, data-dependent read of expert
``block_expert[...]`` at an address only the routing result determines —
while the MXU multiplies tile ``b`` (the Access loop of Listing 4
running ``rif`` tiles ahead of Execute).  The ring spans the whole flat
(token-block, f-tile, d-tile) stream via
:func:`~repro.kernels.ring.ring_step`, so the prefetch depth crosses
expert boundaries instead of being whatever the Pallas pipeliner decides
for a BlockSpec index map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import (RingChannel, clamp_rif,
                                ring_scratch_shapes, ring_step)


def _gmm_kernel(be_ref, x_ref, w_hbm, o_ref, acc, wscr, wsem, *,
                nb: int, nf: int, nd: int, bd: int, bf: int, rif: int):
    b = pl.program_id(0)
    kd = jax.lax.rem(b, nd)

    def src(q):
        # Decode the flat tile index the Access loop is fetching for (q
        # runs up to ``rif`` ahead of ``b``), then read the expert id out
        # of the scalar-prefetched routing stream — the data-dependent
        # request address.
        ti = q // (nf * nd)
        jf = jax.lax.rem(q // nd, nf)
        kk = jax.lax.rem(q, nd)
        return w_hbm.at[pl.ds(be_ref[ti], 1), pl.ds(kk * bd, bd),
                        pl.ds(jf * bf, bf)]

    ring = RingChannel(wscr, wsem, rif, src=src)

    def execute(w_tile):
        @pl.when(kd == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_tile[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(kd == nd - 1)
        def _flush():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    ring_step([ring], b, nb, execute)


def gmm(x: jax.Array, w: jax.Array, block_expert: jax.Array, *, bt: int,
        bf: int, bd: int, rif: int, interpret: bool) -> jax.Array:
    """x (T, D) sorted by expert, T % bt == 0; w (E, D, F);
    block_expert (T//bt,) int32.  Returns (T, F).  ``rif`` expert weight
    tiles stream ahead of the consuming grid step."""
    t, d = x.shape
    e, _, f = w.shape
    ntb, nf, nd = t // bt, f // bf, d // bd
    nb = ntb * nf * nd
    rif = clamp_rif(rif, nb)
    kernel = functools.partial(_gmm_kernel, nb=nb, nf=nf, nd=nd, bd=bd,
                               bf=bf, rif=rif)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((bt, bd),
                             lambda b, be: (b // (nf * nd), b % nd)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (bt, bf), lambda b, be: (b // (nf * nd), (b // nd) % nf)),
            scratch_shapes=[
                pltpu.VMEM((bt, bf), jnp.float32),
                *ring_scratch_shapes(rif, (1, bd, bf), w.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        interpret=interpret,
    )(block_expert, x, w)
