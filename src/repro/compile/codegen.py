"""Pass 4 — codegen: instantiate ring kernels + the store epilogue.

The checked IR lowers onto the three templates in
:mod:`repro.kernels.compiled`:

  * every surviving STATIC channel   -> one :func:`ring_gather` call;
  * every INDIRECT channel + source  -> one :func:`ring_deref` call
    (the source's landed values come back from phase 1);
  * a ChaseSpec program              -> one :func:`ring_chase` call.

What remains on the host is the *store epilogue*: the traced
:class:`~repro.compile.ir.StoreIR` events replayed in program order,
each copy store reading its channel's landed row, each const store its
partially-evaluated value.  That replay is pure bookkeeping — every
byte that moves, moves through a ring on the device.

Each kernel invocation is wrapped in ``jax.jit`` once at compile time,
so repeated :meth:`CompiledKernel.__call__`\\ s (the bench loop) pay no
retrace.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile.check import CheckResult, _norm_value
from repro.compile.infer import ChannelPlan
from repro.compile.ir import ChannelIR, ChaseSpec, DaeIR, StreamKind
from repro.kernels.compiled import ring_chase, ring_deref, ring_gather

__all__ = ["CompiledKernel", "codegen"]


def _padded_addrs(addrs: List[int], chunk: int) -> np.ndarray:
    m = len(addrs)
    mp = -(-m // chunk) * chunk
    out = np.zeros(mp, np.int32)          # pad fetches row 0; sliced off
    out[:m] = addrs
    return out


def _gather_runner(ir: DaeIR, c: ChannelIR, plan: ChannelPlan,
                   interpret: bool) -> Callable[[], Dict[str, Any]]:
    port_j = jnp.asarray(ir.ports[c.port].array)
    addrs_j = jnp.asarray(_padded_addrs(c.addrs, plan.chunk))
    fn = jax.jit(functools.partial(ring_gather, chunk=plan.chunk,
                                   rif=plan.rif, interpret=interpret))
    name, m = c.name, c.count

    def run() -> Dict[str, Any]:
        return {name: np.asarray(fn(port_j, addrs_j))[:m]}
    return run


def _deref_runner(ir: DaeIR, src: ChannelIR, c: ChannelIR,
                  src_plan: ChannelPlan, plan: ChannelPlan,
                  interpret: bool) -> Callable[[], Dict[str, Any]]:
    a_j = jnp.asarray(ir.ports[src.port].array)
    b_j = jnp.asarray(ir.ports[c.port].array)
    chunk = plan.chunk
    addrs_j = jnp.asarray(_padded_addrs(src.addrs, chunk))
    fn = jax.jit(functools.partial(
        ring_deref, chunk=chunk, rif_a=src_plan.rif, rif_b=plan.rif,
        offset=c.offset, interpret=interpret))
    names, m = (src.name, c.name), c.count

    def run() -> Dict[str, Any]:
        out_a, out_b = fn(a_j, b_j, addrs_j)
        return {names[0]: np.asarray(out_a)[:m],
                names[1]: np.asarray(out_b)[:m]}
    return run


def _chase_runner(ir: DaeIR, spec: ChaseSpec, plan: ChannelPlan,
                  interpret: bool) -> Callable[[], Dict[str, Any]]:
    m, s = spec.n_items, spec.state_width
    chunk = max(1, min(plan.chunk, m))     # plan.chunk sized on requests
    rif = max(1, min(plan.rif, chunk))     # = items x levels; re-clamp
    mp = -(-m // chunk) * chunk
    state0 = np.zeros((mp, s), np.int32)
    state0[:m] = spec.state0.astype(np.int32)
    if mp > m:
        state0[m:] = state0[0]             # pad items shadow item 0
    port_j = jnp.asarray(ir.ports[spec.port].array)
    flat_j = jnp.asarray(state0.reshape(-1))
    fn = jax.jit(functools.partial(
        ring_chase, chunk=chunk, rif=rif, max_steps=spec.max_steps,
        s_width=s, addr_fn=spec.addr_fn, step_fn=spec.step_fn,
        out_fn=spec.out_fn, interpret=interpret))

    def run() -> Dict[str, Any]:
        oa, ov = fn(port_j, flat_j)
        return {"__chase__": (np.asarray(oa)[:m], np.asarray(ov)[:m])}
    return run


@dataclasses.dataclass
class CompiledKernel:
    """A runnable compiled program: call it, get the output ports.

    ``__call__`` runs every ring kernel (device), then the store
    epilogue (host), and returns ``{out port: np.ndarray}`` — width-1
    ports as 1-D arrays, matching what
    :meth:`SimResult.stored_array`-style oracles produce.
    """

    name: str
    shape: str                              # 'gather' | 'deref' | 'chase'
    ir: DaeIR
    plans: Dict[str, ChannelPlan]
    out_specs: Dict[str, Tuple[int, int, Any]]
    interpret: bool
    chase: Optional[ChaseSpec] = None
    runners: List[Callable[[], Dict[str, Any]]] = \
        dataclasses.field(default_factory=list)

    def __call__(self) -> Dict[str, np.ndarray]:
        landed: Dict[str, Any] = {}
        for run in self.runners:
            landed.update(run())

        outs: Dict[str, np.ndarray] = {}
        for port, (length, width, dtype) in self.out_specs.items():
            arr = np.zeros((length, width), dtype)
            raw = self.ir.raw_memories.get(port)
            if raw is not None:            # numeric initial contents
                for i, v in enumerate(raw):
                    row = _norm_value(v)
                    if row is not None and len(row) == width:
                        arr[i] = row.astype(dtype)
            outs[port] = arr

        if self.shape == "chase":
            if "__chase__" in landed:
                oa, ov = landed["__chase__"]
                out = outs[self.chase.out_port]
                for a, v in zip(oa, ov):
                    out[int(a), 0] = v
        else:
            for st in self.ir.stores:
                if st.source is not None:
                    cname, k = st.source
                    val = landed[cname][k]
                else:                       # const: partially evaluated
                    val = _norm_value(st.value)
                outs[st.port][st.addr] = np.asarray(val).astype(
                    outs[st.port].dtype)

        return {p: (a[:, 0] if a.shape[1] == 1 else a)
                for p, a in outs.items()}

    def describe(self) -> str:
        lines = [f"CompiledKernel({self.name}) shape={self.shape} "
                 f"interpret={self.interpret}"]
        for p in self.plans.values():
            lines.append(f"  plan {p.channel}: chunk={p.chunk} "
                         f"rif={p.rif} [{p.source}]"
                         + (f" ({p.note})" if p.note else ""))
        lines.append(self.ir.describe())
        return "\n".join(lines)


def codegen(ir: DaeIR, chk: CheckResult,
            plans: Dict[str, ChannelPlan], *,
            chase: Optional[ChaseSpec] = None,
            interpret: bool) -> CompiledKernel:
    """Instantiate the ring kernels for a checked IR."""
    runners: List[Callable[[], Dict[str, Any]]] = []

    if chk.shape == "chase":
        assert chase is not None
        if ir.channels and chase.n_items > 0:
            (c,) = ir.channels.values()
            runners.append(_chase_runner(ir, chase, plans[c.name],
                                         interpret))
    else:
        consumed = set()
        for c in ir.channels.values():
            if c.kind is StreamKind.INDIRECT and c.count > 0:
                src = ir.channels[c.source]
                runners.append(_deref_runner(
                    ir, src, c, plans[src.name], plans[c.name],
                    interpret))
                consumed.update((src.name, c.name))
        for c in ir.channels.values():
            if (c.name not in consumed
                    and c.kind is StreamKind.STATIC and c.count > 0):
                runners.append(_gather_runner(ir, c, plans[c.name],
                                              interpret))

    return CompiledKernel(
        name=ir.name, shape=chk.shape, ir=ir, plans=plans,
        out_specs=chk.out_specs, interpret=interpret, chase=chase,
        runners=runners)
