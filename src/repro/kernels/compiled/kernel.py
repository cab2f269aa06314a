"""The compiler's kernel templates: three ring shapes cover the IR.

``repro.compile.codegen`` lowers every compilable :class:`DaeIR` onto
one of three Pallas templates, all emitted through the shared
:mod:`repro.kernels.ring` scaffolds (so the §5.1 conservation structure
and the §5.3 capacity bound are inherited, not re-implemented):

* :func:`ring_gather` — a STATIC address stream: the scalar-prefetched
  Access loop of ``dae_gather``'s explicit-RIF variant, generalized to
  any (N, W) port.
* :func:`ring_deref`  — one INDIRECT hop (``b[a[i]]``): phase 1 rings
  the index port and banks the landed scalars in SMEM, phase 2 rings
  the data port through them.  Two ``access_execute`` loops per grid
  step; the SMEM bank is the inter-loop channel.
* :func:`ring_chase`  — a DEPENDENT stream driven by a
  :class:`~repro.compile.ir.ChaseSpec`: per-item int32 state in SMEM, a
  lock-step level loop (Listing 5's fixed-length form — every item
  walks ``max_steps`` levels, redundant tail loads included), each
  level a full ``access_execute`` whose ``src`` reads the state the
  previous level wrote.

All three process ``chunk`` items per grid step with ``rif`` copies in
flight and expect item counts pre-padded to a chunk multiple (the
compiler pads with index 0 / replicated state and slices the pad off on
the host).  The templates are written for interpret-mode parity first;
lane-width alignment of ``W`` is the caller's concern on real TPUs.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring import RingChannel, access_execute, \
    ring_scratch_shapes

__all__ = ["ring_gather", "ring_deref", "ring_chase"]


# ---------------------------------------------------------------------------
# shape 1: STATIC stream — scalar-prefetch gather over any (N, W) port
# ---------------------------------------------------------------------------


def _gather_kernel(addr_ref, port_hbm, out_ref, scratch, sems, *,
                   chunk: int, rif: int):
    c = pl.program_id(0)
    base = c * chunk
    ring = RingChannel(
        scratch, sems, rif,
        src=lambda k: port_hbm.at[pl.ds(addr_ref[base + k], 1), :])

    def execute(k, row):
        out_ref[pl.ds(k, 1), :] = row

    access_execute([ring], chunk, execute)


def ring_gather(port: jax.Array, addrs: jax.Array, *, chunk: int,
                rif: int, interpret: bool) -> jax.Array:
    """Fetch ``port[addrs]`` — ``port`` (N, W), ``addrs`` (M,) int32
    with M a multiple of ``chunk``.  Returns (M, W)."""
    m = addrs.shape[0]
    n, w = port.shape
    assert m % chunk == 0, (m, chunk)

    kernel = functools.partial(_gather_kernel, chunk=chunk, rif=rif)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk, w), lambda c, a: (c, 0)),
            scratch_shapes=[*ring_scratch_shapes(rif, (1, w), port.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, w), port.dtype),
        interpret=interpret,
    )(addrs, port)


# ---------------------------------------------------------------------------
# shape 2: one INDIRECT hop — b[a[i] + offset] via an SMEM address bank
# ---------------------------------------------------------------------------


def _deref_kernel(addr_ref, a_hbm, b_hbm, out_a_ref, out_b_ref,
                  addr_s, scr_a, sem_a, scr_b, sem_b, *,
                  chunk: int, rif_a: int, rif_b: int, offset: int,
                  nb: int):
    c = pl.program_id(0)
    base = c * chunk

    ring_a = RingChannel(
        scr_a, sem_a, rif_a,
        src=lambda k: a_hbm.at[pl.ds(addr_ref[base + k], 1), :])

    def land_a(k, row):
        out_a_ref[pl.ds(k, 1), :] = row
        # The landed scalar IS the next address (check guarantees the
        # true-run addresses were in range; the clip only disciplines
        # the perturbed-ghost values a real run never produces).
        addr_s[k] = jnp.clip(row[0, 0] + offset, 0, nb - 1)

    access_execute([ring_a], chunk, land_a)

    ring_b = RingChannel(
        scr_b, sem_b, rif_b,
        src=lambda k: b_hbm.at[pl.ds(addr_s[k], 1), :])

    def land_b(k, row):
        out_b_ref[pl.ds(k, 1), :] = row

    access_execute([ring_b], chunk, land_b)


def ring_deref(port_a: jax.Array, port_b: jax.Array, addrs: jax.Array,
               *, chunk: int, rif_a: int, rif_b: int, offset: int = 0,
               interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Two-phase ring: ``va = a[addrs]`` then ``vb = b[va + offset]``.
    ``port_a`` is (NA, 1) int32; returns ((M, 1) int32, (M, WB))."""
    m = addrs.shape[0]
    na, wa = port_a.shape
    nb, wb = port_b.shape
    assert wa == 1, wa
    assert m % chunk == 0, (m, chunk)

    kernel = functools.partial(_deref_kernel, chunk=chunk, rif_a=rif_a,
                               rif_b=rif_b, offset=offset, nb=nb)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((chunk, 1), lambda c, a: (c, 0)),
                pl.BlockSpec((chunk, wb), lambda c, a: (c, 0)),
            ],
            scratch_shapes=[
                pltpu.SMEM((chunk,), jnp.int32),
                *ring_scratch_shapes(rif_a, (1, 1), port_a.dtype),
                *ring_scratch_shapes(rif_b, (1, wb), port_b.dtype),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((m, 1), port_a.dtype),
                   jax.ShapeDtypeStruct((m, wb), port_b.dtype)],
        interpret=interpret,
    )(addrs, port_a, port_b)


# ---------------------------------------------------------------------------
# shape 3: DEPENDENT stream — lock-step chase driven by a ChaseSpec
# ---------------------------------------------------------------------------


def _chase_kernel(state0_ref, port_hbm, out_addr_ref, out_val_ref,
                  state_s, scratch, sems, *, chunk: int, rif: int,
                  max_steps: int, n: int, s_width: int,
                  addr_fn: Callable, step_fn: Callable,
                  out_fn: Callable):
    c = pl.program_id(0)
    base = c * chunk

    def state_at(k):
        return tuple(state_s[k, j] for j in range(s_width))

    def init(k, _):
        for j in range(s_width):
            state_s[k, j] = state0_ref[(base + k) * s_width + j]
        return 0

    jax.lax.fori_loop(0, chunk, init, 0)

    ring = RingChannel(
        scratch, sems, rif,
        src=lambda k: port_hbm.at[
            pl.ds(jnp.clip(addr_fn(state_at(k)), 0, n - 1)
                  .astype(jnp.int32), 1), :])

    def execute(k, row):
        new = step_fn(state_at(k), row[0])
        for j in range(s_width):
            state_s[k, j] = jnp.asarray(new[j]).astype(jnp.int32)

    # Listing 5: every item walks exactly max_steps levels; finished
    # items issue redundant (clipped) tail loads, which is what buys
    # the lock-step schedule its full-RIF overlap.
    def level(_, carry):
        access_execute([ring], chunk, execute)
        return carry

    jax.lax.fori_loop(0, max_steps, level, 0)

    def emit(k, _):
        oa, ov = out_fn(state_at(k))
        out_addr_ref[pl.ds(k, 1)] = jnp.asarray(oa).astype(jnp.int32)[None]
        out_val_ref[pl.ds(k, 1)] = jnp.asarray(ov).astype(jnp.int32)[None]
        return 0

    jax.lax.fori_loop(0, chunk, emit, 0)


def ring_chase(port: jax.Array, state0_flat: jax.Array, *, chunk: int,
               rif: int, max_steps: int, s_width: int,
               addr_fn: Callable, step_fn: Callable, out_fn: Callable,
               interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Walk a dependent-load chase for M items (``state0_flat`` is the
    row-major (M*S,) int32 initial state, M a multiple of ``chunk``).
    Returns per-item ``(store_addr, store_value)`` int32 vectors."""
    n, _w = port.shape
    m = state0_flat.shape[0] // s_width
    assert state0_flat.shape[0] == m * s_width
    assert m % chunk == 0, (m, chunk)

    kernel = functools.partial(
        _chase_kernel, chunk=chunk, rif=rif, max_steps=max_steps, n=n,
        s_width=s_width, addr_fn=addr_fn, step_fn=step_fn, out_fn=out_fn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((chunk,), lambda c, s: (c,)),
                pl.BlockSpec((chunk,), lambda c, s: (c,)),
            ],
            scratch_shapes=[
                pltpu.SMEM((chunk, s_width), jnp.int32),
                *ring_scratch_shapes(rif, (1, port.shape[1]), port.dtype),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((m,), jnp.int32),
                   jax.ShapeDtypeStruct((m,), jnp.int32)],
        interpret=interpret,
    )(state0_flat, port)
