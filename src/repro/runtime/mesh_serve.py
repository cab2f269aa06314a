"""Mesh-sharded decoupled serving: the paged pipeline over devices.

:class:`ShardedPagedServeLoop` is :class:`~repro.runtime.serve_loop.
PagedServeLoop` with its engines *placed*: the KV page pool shards its
page dim over the decode mesh's ``data`` axis (``_PAGED_POOL`` rule in
``parallel/sharding.py`` plus the in-jit ``_pool_constraint`` in
``models/attention.py``), page tables ride
:func:`~repro.parallel.sharding.page_table_sharding`, and the
engine-joining channels become
:class:`~repro.channels.mesh.MeshChannel` rings — control messages
physically travel the mesh via collective_permute.

Two placements (:func:`~repro.launch.mesh.make_serve_meshes`):

  * **co-located** — one mesh runs both engines; n=1 degenerates to a
    computation bit-identical to ``PagedServeLoop`` (pinned per
    attention family by tests/test_sharded_serve.py and the
    ``serve/sharded/mesh1`` bench cell).
  * **disaggregated** — Access (prefill) and Execute (decode) run on
    disjoint submeshes joined *only* by mesh channels over the union
    mesh's ``role`` axis.  Prefill writes a private staging pool sized
    ``1 + b*npb`` (a concurrent prefill can never run it dry); on
    prompt completion the slot's pages migrate to the decode pool in
    pool layout — gather on the prefill mesh, host hop, scatter on the
    decode mesh (``bundle.gather_pages``/``scatter_pages``), padded to
    ``npb`` with trash page 0 so one jit covers every prompt length.
    If the decode pool cannot back the migration even after preemption
    escalation, the slot preempts *itself* and re-enters admission
    (teacher-forced resume keeps outputs bit-identical).  Prefix reuse
    is forced off: staging pages are transient, so cross-request
    sharing would dangle across the migration.

Families without paged primitives (recurrent state) keep the
contiguous shared-cache path of the base class — both engines then
drive one dense cache and only the control channels are mesh-placed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.channels import MeshChannel
from repro.core.trace import Tracer
from repro.launch.mesh import ServeMeshes, make_serve_meshes
from repro.models.registry import build_model
from repro.parallel.sharding import (ShardingRules, cache_shardings,
                                     page_table_sharding, param_shardings)
from repro.runtime.serve_loop import (PageAllocator, PagedServeLoop,
                                      _AdmitChannel, _shared_jit)

__all__ = ["ShardedPagedServeLoop"]


class ShardedPagedServeLoop(PagedServeLoop):
    """Paged decoupled serving with device placement (module docstring).

    ``meshes`` defaults to a single-device co-located placement (the
    bit-parity configuration); ``rules`` default to replicated params
    and no sequence sharding — serving shards the *pool*, and keeping
    params whole makes the sharded loop's outputs exactly match the
    single-host loop's.
    """

    def __init__(self, cfg, bundle, params, batch_slots: int, s_max: int,
                 meshes: Optional[ServeMeshes] = None,
                 rules: Optional[ShardingRules] = None, **kw):
        self.meshes = meshes if meshes is not None else make_serve_meshes(1)
        self.rules = rules if rules is not None else \
            ShardingRules(fsdp=False, seq_shard_cache=False)
        self._disagg = self.meshes.disaggregated
        self._engine = "execute"
        if self._disagg:
            kw["prefix_reuse"] = False
        dm_size = int(np.prod(list(self.meshes.decode.shape.values())))
        self._place = dm_size > 1
        if self._place and cfg.mesh_pool_axis is None:
            cfg = dataclasses.replace(cfg, mesh_pool_axis=self.meshes.axis)
            bundle = build_model(cfg)
        super().__init__(cfg, bundle, params, batch_slots, s_max, **kw)

    # -- placement -----------------------------------------------------------

    def _make_channels(self) -> None:
        self.admit_q = _AdmitChannel("admit", self._admit_capacity,
                                     self.tracer)
        if self._disagg:
            um, ax = self.meshes.union, self.meshes.role_axis
            self.handoff = MeshChannel("prefill_done", self.b, um, ax,
                                       src=0, dst=1, tracer=self.tracer)
            self.free_slots = MeshChannel("free_slots", self.b, um, ax,
                                          src=1, dst=0, tracer=self.tracer)
        else:
            dm = self.meshes.decode
            span = int(dm.shape[self.meshes.axis])
            self.handoff = MeshChannel("prefill_done", self.b, dm,
                                       self.meshes.axis, src=0,
                                       dst=span - 1, tracer=self.tracer)
            self.free_slots = MeshChannel("free_slots", self.b, dm,
                                          self.meshes.axis, src=span - 1,
                                          dst=0, tracer=self.tracer)

    def _pool_on(self, mesh, n_pages: int):
        """A zeroed page pool built directly in its sharded placement on
        ``mesh``, so no device ever holds the whole pool on the way."""
        init = functools.partial(self.bundle.cache_init_paged, self.b,
                                 n_pages, self.page)
        shardings = cache_shardings(jax.eval_shape(init), mesh, self.rules)
        return jax.jit(init, out_shardings=shardings)()

    def _new_pool(self, n_pages: int):
        if self._place:
            return self._pool_on(self.meshes.decode, n_pages)
        return super()._new_pool(n_pages)

    def _make_cache(self) -> None:
        super()._make_cache()
        if not self.paged:
            return
        if self._place:
            dm = self.meshes.decode
            self.params = jax.device_put(
                self.params, param_shardings(self.params, dm, self.rules))
            self._table_sh = page_table_sharding(dm, self.b, self.rules)
        if self._disagg:
            pm = self.meshes.prefill
            self._params_pf = jax.device_put(
                self.params, param_shardings(self.params, pm, self.rules))
            # staging pool: every slot holds at most npb pages, so
            # 1 + b*npb (trash page + b horizons) can never run dry;
            # rounded up so the page dim divides over the prefill mesh
            span = int(np.prod(list(pm.shape.values())))
            self.n_pages_pf = -(-(1 + self.b * self.npb) // span) * span
            self.alloc_pf = PageAllocator(self.n_pages_pf, self.page)
            self.table_pf = np.zeros((self.b, self.npb), np.int32)
            self.n_blocks_pf = np.zeros(self.b, np.int64)
            self.cache_pf = self._pool_on(pm, self.n_pages_pf)
            self._gather = _shared_jit(self.bundle.gather_pages)
            self._scatter = _shared_jit(self.bundle.scatter_pages,
                                        donate=(0,))

    # -- engine routing ------------------------------------------------------

    def _prefill_step(self, t0, results) -> None:
        self._engine = "access"
        try:
            super()._prefill_step(t0, results)
        finally:
            self._engine = "execute"

    def _step(self, tok, n_valid):
        if not self.paged:
            return super()._step(tok, n_valid)
        if self._disagg and self._engine == "access":
            saved = (self.params, self.cache, self.table)
            self.params = self._params_pf
            self.cache = self.cache_pf
            self.table = self.table_pf
            try:
                with jax.set_mesh(self.meshes.prefill):
                    return super()._step(tok, n_valid)
            finally:
                self.cache_pf = self.cache
                self.params, self.cache, self.table = saved
        tbl = self.table
        if self._place:
            self.table = jax.device_put(np.asarray(tbl), self._table_sh)
        try:
            with jax.set_mesh(self.meshes.decode):
                return super()._step(tok, n_valid)
        finally:
            self.table = tbl

    # -- disaggregated page life cycle ---------------------------------------

    def _release_pf(self, slot: int) -> None:
        for i in range(int(self.n_blocks_pf[slot])):
            self.alloc_pf.decref(int(self.table_pf[slot, i]))
            self.table_pf[slot, i] = 0
        self.n_blocks_pf[slot] = 0

    def _prefill_grant(self, slot: int, ptr: int, n: int) -> int:
        if not (self.paged and self._disagg):
            return super()._prefill_grant(slot, ptr, n)
        if n <= 0:
            return n
        last_blk = (ptr + n - 1) // self.page
        while self.n_blocks_pf[slot] <= last_blk:
            pg = self.alloc_pf.alloc()
            assert pg is not None, "staging pool sized to never run dry"
            self.table_pf[slot, int(self.n_blocks_pf[slot])] = pg
            self.n_blocks_pf[slot] += 1
            self.stats.page_allocs += 1
        return n

    def _on_prompt_complete(self, slot: int) -> None:
        if not (self.paged and self._disagg):
            return super()._on_prompt_complete(slot)
        # migrate the finished prompt's staging pages into the decode
        # pool; on failure the slot preempts itself (the base
        # _prefill_step guard skips its handoff)
        nb = int(self.n_blocks_pf[slot])
        dst: List[int] = []
        for _ in range(nb):
            # _alloc_page may preempt *other* (strictly younger) slots;
            # this slot's staging pages and phase are untouched by that
            pg = self._alloc_page(slot)
            if pg is None:
                for p in dst:
                    self.alloc.decref(p)
                self._preempt(slot)
                return
            dst.append(pg)
        src = [int(self.table_pf[slot, i]) for i in range(nb)]
        self._migrate(src, dst, slot, int(self.pos[slot]))
        for i, p in enumerate(dst):
            self.table[slot, i] = p
        self.n_blocks[slot] = nb
        self._release_pf(slot)

    def _migrate(self, src: List[int], dst: List[int], slot: int,
                 new_len: int) -> None:
        """Move pages ``src`` (staging pool) to ``dst`` (decode pool)
        in pool layout, padded to ``npb`` with trash page 0 (reading
        page 0 is garbage that is never attended; writing it is
        allowed by definition)."""
        pad = self.npb - len(src)
        src_a = jnp.asarray(src + [0] * pad, jnp.int32)
        dst_a = jnp.asarray(dst + [0] * pad, jnp.int32)
        with jax.set_mesh(self.meshes.prefill):
            blocks = self._gather(self.cache_pf, src_a)
        blocks = jax.device_get(blocks)          # prefill -> decode hop
        with jax.set_mesh(self.meshes.decode):
            self.cache = self._scatter(self.cache, blocks, dst_a,
                                       np.int32(slot), np.int32(new_len))
        self.stats.migrations += 1

    def _preempt(self, victim: int) -> None:
        if self.paged and self._disagg:
            self._release_pf(victim)
        super()._preempt(victim)

    def _reset_slots(self, reset, keep, new_lens) -> None:
        if self.paged and self._disagg:
            self.table_pf[reset, :] = 0          # freed rows stay zeroed
            self.cache_pf = self._reset_paged(
                self.cache_pf, jnp.asarray(keep),
                jnp.asarray(new_lens, jnp.int32))
        super()._reset_slots(reset, keep, new_lens)
