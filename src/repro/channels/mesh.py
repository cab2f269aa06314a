"""Mesh transport: a shard_map ring over a named mesh axis.

The decoupled serving pipeline's cross-chip edge.  Each channel owns a
fixed-size device ring buffer — one ``(capacity, width)`` int32 row per
device along ``axis`` — and ``push`` physically moves the payload from
the ``src`` device row to the ``dst`` device row with
``jax.lax.ppermute`` (collective_permute, the same neighbor-move that
drives ``parallel/pp.py``'s pipeline); ``pop`` reads the landed entry
out of the destination row.  With span 1 the permutation is the
identity ``[(0, 0)]`` and the transport degenerates to a single-device
queue — the serve parity tests pin that case bit-identical to
:class:`~repro.channels.local.LocalChannel`.

Division of labor: payload *values* travel the device ring; head/tail
cursors, occupancy (backpressure) and each entry's Python shape (bare
int vs tuple arity) are host-side control plane, exactly like the
serve scheduler that drives the channel.  Tracing follows the shared
vocabulary (post-event depth, see ``base.py``).
"""

from __future__ import annotations

from collections import deque
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from repro.channels.base import ChannelBase

_I32 = 2 ** 31


class MeshChannel(ChannelBase):
    """Bounded FIFO whose entries travel ``src -> dst`` along a mesh
    axis via collective_permute.

    Entries are ints or (short) tuples of ints — the pipeline's control
    messages (slot ids, first tokens).  ``width`` bounds the tuple
    arity; ``capacity`` is the ring depth on every device.
    """

    transport = "mesh"

    def __init__(self, name: str, capacity: int, mesh: Mesh,
                 axis: str = "data", *, src: int = 0,
                 dst: Optional[int] = None, width: int = 2,
                 tracer=None, instance: str = "serve"):
        if capacity is None or capacity < 1:
            raise ValueError("MeshChannel needs a finite capacity >= 1 "
                             "(it is a fixed-size device ring buffer)")
        if axis not in mesh.axis_names:
            raise ValueError(
                f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        super().__init__(name, capacity, tracer, instance)
        self.mesh = mesh
        self.axis = axis
        self.width = width
        self.span = int(mesh.shape[axis])
        self.src = int(src) % self.span
        self.dst = int(self.span - 1 if dst is None else dst) % self.span
        self._buf_sh = NamedSharding(mesh, P(axis, None, None))
        self._pay_sh = NamedSharding(mesh, P(axis, None))
        self._buf = jax.device_put(
            np.zeros((self.span, capacity, width), np.int32), self._buf_sh)
        self._send = self._build_send()
        self._head = 0
        self._tail = 0
        self._count = 0
        self._meta: deque = deque()      # (kind, arity) per in-flight entry

    def _build_send(self):
        axis, src, dst = self.axis, self.src, self.dst

        def body(buf, pay, tail):
            # per-device blocks: buf (1, capacity, width), pay (1, width)
            moved = jax.lax.ppermute(pay, axis, [(src, dst)])
            idx = jax.lax.axis_index(axis)
            row = buf[0].at[tail].set(moved[0])
            return jnp.where(idx == dst, row, buf[0])[None]

        sm = _shard_map(body, mesh=self.mesh,
                        in_specs=(P(axis), P(axis), P()),
                        out_specs=P(axis))
        return jax.jit(sm, donate_argnums=0)

    # -- wire format ---------------------------------------------------------

    def _encode(self, item: Any) -> Tuple[str, Tuple[int, ...]]:
        if isinstance(item, (int, np.integer)):
            vals: Tuple[int, ...] = (int(item),)
            kind = "i"
        elif isinstance(item, (tuple, list)):
            vals = tuple(int(v) for v in item)
            kind = "t"
        else:
            raise TypeError(
                f"mesh transport carries int / tuple-of-int control "
                f"messages, got {type(item).__name__}")
        if len(vals) > self.width:
            raise ValueError(f"entry arity {len(vals)} exceeds channel "
                             f"width {self.width}")
        for v in vals:
            if not -_I32 <= v < _I32:
                raise ValueError(f"entry value {v} does not fit int32")
        return kind, vals

    def _read(self, slot: int, kind: str, arity: int) -> Any:
        row = np.asarray(jax.device_get(self._buf))[self.dst, slot]
        if kind == "i":
            return int(row[0])
        return tuple(int(v) for v in row[:arity])

    # -- protocol surface ----------------------------------------------------

    def push(self, item: Any) -> bool:
        if self._count >= self.capacity:
            return False
        kind, vals = self._encode(item)
        pay = np.zeros((self.span, self.width), np.int32)
        pay[self.src, :len(vals)] = vals
        self._buf = self._send(self._buf,
                               jax.device_put(pay, self._pay_sh),
                               np.int32(self._tail))
        self._tail = (self._tail + 1) % self.capacity
        self._meta.append((kind, len(vals)))
        self._count += 1
        self._trace(self._count)
        return True

    def pop(self) -> Any:
        if not self._count:
            raise IndexError(f"pop from empty mesh channel {self.name!r}")
        kind, arity = self._meta.popleft()
        item = self._read(self._head, kind, arity)
        self._head = (self._head + 1) % self.capacity
        self._count -= 1
        self._trace(self._count)
        return item

    def peek(self) -> Any:
        if not self._count:
            raise IndexError(f"peek at empty mesh channel {self.name!r}")
        kind, arity = self._meta[0]
        return self._read(self._head, kind, arity)

    def __len__(self) -> int:
        return self._count
