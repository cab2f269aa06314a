"""Decoupled Access/Execute serving pipeline (paper §3 applied to serving).

The legacy loop (kept below as :class:`LegacyServeLoop`) admitted each
request by feeding its prompt one token at a time through the
*full-batch* decode step: admitting a P-token prompt cost P full-batch
rounds during which every already-active slot was stalled — and, worse,
each warmup round also ran the decode step for the stalled slots,
scattering their current token into their KV caches once per prompt
token and never resetting a recycled slot's cache length.  That loop is
the textbook *coupled* access/execute program of DAE4HLS §3: one
lock-step stream in which a slow access (prefill) serializes everything
behind it.

The rewrite splits serving into two engines joined by explicit bounded
channels (the ``repro.core`` channel/occupancy vocabulary — the same
:class:`~repro.core.trace.Tracer` that profiles the DAE simulator
profiles serving):

    requests ──admit──▶ [ACCESS: admission + chunked batched prefill]
                 │                    │
                 │              prefill_done (first token rides along)
                 │                    ▼
                 └─◀─free_slots── [EXECUTE: dense batched decode] ──▶ results

Both engines drive ONE compiled primitive, ``bundle.prefill``:

  * the Access engine advances every admitting slot by up to ``chunk``
    prompt tokens per step (one call, all slots batched) — admitting a
    P-token prompt costs ceil(P / chunk) steps instead of P;
  * the Execute engine calls the same primitive at chunk width 1 with a
    0/1 per-slot valid mask — a *masked* decode step under which
    inactive and mid-prefill slots are provably untouched (validity
    gates every cache scatter and recurrent-state update).

The scheduler interleaves them one step per round, so the dense decode
stream never stalls for more than a single prefill chunk.  ``run`` is
open-loop: each :class:`Request` carries a ``t_arrival`` offset (seconds
from run start, default 0 = closed-loop batch) and is only released to
the admit channel once that time has passed; TTFT is measured from each
request's own arrival, not from run start.

:class:`PagedServeLoop` rebuilds the same pipeline on *paged* KV (the
explicit-decoupling lesson applied to the serving memory system): KV
lives in a pool of fixed-size pages owned by a :class:`PageAllocator`
free-list, each slot addresses its logical sequence through a per-slot
page table, and decode in ``pallas`` mode drives
``flash_decode_paged``'s ring gather over the scalar-prefetched table.
Slot recycling becomes page recycling; refcounted pages enable
hash-keyed prompt-prefix reuse (:class:`PrefixCache`) with
copy-on-write on divergence; admission is preemption-aware — a request
that cannot get pages is parked at the head of the admit channel, and a
slot that cannot extend under memory pressure preempts the *youngest*
slot back to the admit queue (recompute-style resume, teacher-forced,
bit-identical outputs) instead of deadlocking.  Families with recurrent
state (SSM/RWKV/hybrid, encdec) have no growing KV to page: the loop
detects ``bundle.cache_init_paged is None`` and falls back to the dense
contiguous path, bit-parity-pinned by the serve tests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.channels import LocalChannel
from repro.core.trace import Tracer

# slot phases
_FREE, _PREFILL, _HANDOFF, _DECODE = 0, 1, 2, 3


def _shared_jit(fn, donate: Tuple[int, ...] = ()):
    """One jit wrapper (and hence one compile cache) per bundle
    function, shared across every loop instance built on that bundle —
    constructing a fresh ServeLoop costs no recompilation.  The wrapper
    is stashed on the function itself so it dies with the bundle.

    ``donate`` names the cache argument of a function that returns the
    new cache: the step then updates the KV pool in place instead of
    holding the old and the new pool at once."""
    jitted = getattr(fn, "_serve_jit", None)
    if jitted is None:
        jitted = jax.jit(fn, donate_argnums=donate)
        fn._serve_jit = jitted
    return jitted


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (P,) int — P may be 0 (treated as [bos])
    max_new: int = 16
    out: Optional[List[int]] = None
    frames: Optional[np.ndarray] = None   # encdec: (S_enc, D) frontend frames
    t_arrival: float = 0.0      # seconds after run() start (open-loop traces)
    t_queued: float = 0.0       # perf_counter() when it last entered admit_q


def _validate_requests(requests: List[Request], s_max: int,
                       encdec: bool = False) -> None:
    """Shared up-front validation: rejecting a request after part of the
    batch was admitted would leave slots mid-flight, and both loops key
    stats/results by rid, so duplicates would silently overwrite."""
    seen = set()
    for req in requests:
        if req.rid in seen:
            raise ValueError(f"duplicate request rid {req.rid}: results "
                             "and stats.ttft are keyed by rid")
        seen.add(req.rid)
        psize = max(1, np.asarray(req.prompt).size)   # empty -> [bos]
        if psize + req.max_new > s_max:
            raise ValueError(
                f"request {req.rid}: prompt ({psize}) + max_new "
                f"({req.max_new}) exceeds s_max ({s_max})")
        if encdec and req.max_new > 0 and req.frames is None:
            raise ValueError(f"request {req.rid}: encdec serving "
                             "requires Request.frames")


# The serving channel moved to repro.channels (one protocol from the
# simulator's Enq/Deq FIFOs to the shard_map mesh ring); ``Channel`` is
# kept as a back-compat alias of the in-process transport.
Channel = LocalChannel


class _AdmitChannel(LocalChannel):
    """The admit queue.  Stamps each request it takes with the time, so
    that admission can count how long the request waited for a slot,
    whoever pushed it (``run``, a preemption, a subclass's client)."""

    __slots__ = ()

    def push(self, item: Request) -> bool:
        if not super().push(item):
            return False
        item.t_queued = time.perf_counter()
        return True


@contextlib.contextmanager
def _span(stats: "ServeStats", name: str, counter: str):
    """A host span: ``name`` on the profiler's trace (where one is being
    recorded) and its seconds added to ``stats.<counter>``."""
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        setattr(stats, counter,
                getattr(stats, counter) + time.perf_counter() - t)


@dataclasses.dataclass
class ServeStats:
    """Counters the serve bench reports; ttft is wall-clock seconds from
    each request's *arrival* (``t_arrival`` after run start) to its
    first emitted token.  The page counters stay 0 on the contiguous
    path."""

    rounds: int = 0
    prefill_steps: int = 0
    decode_steps: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    admitted: int = 0
    ttft: Dict[int, float] = dataclasses.field(default_factory=dict)
    # paged serving
    page_allocs: int = 0
    cow_copies: int = 0
    preemptions: int = 0
    prefix_hits: int = 0
    prefix_tokens_reused: int = 0
    # disaggregated serving: prefill->decode pool page migrations
    migrations: int = 0
    # peak over rounds of sum(prompt + max_new) across concurrently
    # active slots — what a reservation-based contiguous allocator
    # would have had to set aside (the oversubscription witness)
    peak_reserved_tokens: int = 0
    # host seconds by phase of a round, each also a profiler span:
    # serve.admit, serve.prepare (inputs of a step), serve.sample (the
    # decode argmax), serve.emit (outputs of a step); none holds a
    # _step call.  Inside _step: step.dispatch (inputs to the device and
    # the call), step.wait (until the logits are ready), step.copy (the
    # logits to the host, logits_bytes of them).  Flat fields, so that
    # a shallow copy of the stats is a snapshot.
    admit_s: float = 0.0
    prepare_s: float = 0.0
    sample_s: float = 0.0
    emit_s: float = 0.0
    dispatch_s: float = 0.0
    wait_s: float = 0.0
    copy_s: float = 0.0
    logits_bytes: int = 0
    # seconds requests spent in admit_q before a slot took them, and how
    # many were taken (a preempted request counts again)
    queue_wait_s: float = 0.0
    queue_waits: int = 0


class PageAllocator:
    """Free-list allocator over a pool of fixed-size KV pages.

    Page 0 is the reserved *trash page*: page tables default to it, and
    the paged attention path routes every invalid-token scatter there —
    it is never attended to because lengths mask it, so the allocator
    pins it (refcount 1) forever.  Pages are refcounted so the prefix
    cache and multiple adopting slots can share them; ``decref`` returns
    a page to the free list when its last reference drops.
    """

    def __init__(self, n_pages: int, page: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.n_pages = n_pages
        self.page = page
        self.rc = np.zeros(n_pages, np.int32)
        self.rc[0] = 1                       # trash page, permanently pinned
        self.free = deque(range(1, n_pages))

    @property
    def free_count(self) -> int:
        return len(self.free)

    def alloc(self) -> Optional[int]:
        if not self.free:
            return None
        p = self.free.popleft()
        self.rc[p] = 1
        return p

    def incref(self, p: int) -> None:
        self.rc[p] += 1

    def decref(self, p: int) -> None:
        self.rc[p] -= 1
        if self.rc[p] == 0:
            self.free.append(p)


class PrefixCache:
    """Hash-keyed prompt-prefix -> KV-pages map with LRU eviction.

    When a slot finishes prefilling, every page-aligned prefix of its
    fill (plus the final partial length) is registered: the entry holds
    a refcount on each covering page, so the pages survive the slot.  A
    later request whose fill starts with a registered prefix adopts the
    pages outright — its page table points at the shared pages, its
    cache length starts at the matched length, and prefill resumes
    after it.  Divergence inside a shared partial page is handled by
    the serve loop's copy-on-write (the adopter copies the page before
    its first write).  Keys are sha1 over the token bytes; entries also
    keep the tokens and compare them exactly, so a hash collision can
    never adopt wrong KV.  Under page pressure the loop evicts entries
    LRU-first before resorting to preemption.
    """

    def __init__(self) -> None:
        # key -> (length, pages tuple, tokens copy)
        self._entries: "OrderedDict[bytes, Tuple[int, Tuple[int, ...], np.ndarray]]" = OrderedDict()
        self._lens: Dict[int, int] = {}       # length -> #entries of that length

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return hashlib.sha1(
            np.ascontiguousarray(tokens, np.int64).tobytes()).digest()

    def lookup(self, fill: np.ndarray, cap: int, alloc: PageAllocator
               ) -> Tuple[int, List[int]]:
        """Longest registered prefix of ``fill`` with length <= cap.
        On a hit the covering pages are increfed (caller must decref if
        it ends up parking instead of admitting)."""
        for ln in sorted(self._lens, reverse=True):
            if ln > cap or ln > fill.size:
                continue
            key = self._key(fill[:ln])
            entry = self._entries.get(key)
            if entry is None or entry[0] != ln:
                continue
            if not np.array_equal(entry[2], fill[:ln]):
                continue                      # sha1 collision: never adopt
            self._entries.move_to_end(key)
            pages = list(entry[1])
            for p in pages:
                alloc.incref(p)
            return ln, pages
        return 0, []

    def register(self, fill: np.ndarray, length: int, pages: List[int],
                 alloc: PageAllocator) -> bool:
        key = self._key(fill[:length])
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        for p in pages:
            alloc.incref(p)
        self._entries[key] = (length, tuple(pages), fill[:length].copy())
        self._lens[length] = self._lens.get(length, 0) + 1
        return True

    def evict_lru(self, alloc: PageAllocator) -> bool:
        if not self._entries:
            return False
        _, (length, pages, _) = self._entries.popitem(last=False)
        self._lens[length] -= 1
        if not self._lens[length]:
            del self._lens[length]
        for p in pages:
            alloc.decref(p)
        return True


class ServeLoop:
    """Continuous batching with decoupled chunked prefill (Access) and
    dense masked decode (Execute).

    ``chunk`` is the Access engine's tokens-per-step (the decoupling
    knob: larger chunks amortize dispatch, smaller chunks bound the
    decode stream's stall).  ``tracer`` (a ``repro.core.trace.Tracer``)
    records channel occupancy; ``stats`` counts steps/tokens, TTFT, the
    admit queue's waits and the host seconds of each phase of a round,
    each phase also a ``jax.profiler`` span (see :class:`ServeStats`).
    Encoder-decoder bundles are served too: requests carry ``frames``,
    encoded once at admission into a per-slot encoder-output buffer.
    ``record_logits`` keeps, per request id, the float32 logits row each
    emitted token was chosen from (``token_logits``), so a run can be
    compared with another kernel mode or a reference.
    """

    def __init__(self, cfg, bundle, params, batch_slots: int, s_max: int,
                 eos_id: int = -1, chunk: int = 32, bos_id: int = 0,
                 tracer: Optional[Tracer] = None,
                 admit_capacity: Optional[int] = None,
                 record_logits: bool = False):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        self.cfg = cfg
        self.bundle = bundle
        self.params = params
        self.b = batch_slots
        self.s_max = s_max
        self.eos = eos_id
        self.chunk = chunk
        self.bos = bos_id
        self.tracer = tracer
        self.pos = np.zeros(batch_slots, np.int32)
        self.cur = np.zeros(batch_slots, np.int32)
        self.remaining = np.zeros(batch_slots, np.int64)
        self.phase = np.full(batch_slots, _FREE, np.int8)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self._ptr = np.zeros(batch_slots, np.int64)     # prefill progress
        self._psize = np.zeros(batch_slots, np.int64)   # original prompt size
        self._prompt: List[Optional[np.ndarray]] = [None] * batch_slots
        self.token_logits: Optional[Dict[int, List[np.ndarray]]] = \
            {} if record_logits else None

        self.paged = False
        self._make_cache()

        self._encdec = cfg.family == "encdec"
        if self._encdec:
            self._encode = _shared_jit(bundle.encode)
            self.enc_out = None                         # allocated lazily

        # explicit bounded channels between the engines
        self._admit_capacity = admit_capacity
        self._make_channels()
        for s in range(batch_slots):
            self.free_slots.push(s)
        self._overflow: deque = deque()     # beyond admit_q capacity
        self.stats = ServeStats()

    def _make_channels(self) -> None:
        """Engine-joining channels; the sharded loop overrides to place
        handoff/free_slots on a mesh transport."""
        self.admit_q = _AdmitChannel("admit", self._admit_capacity,
                                     self.tracer)
        self.handoff = Channel("prefill_done", self.b, self.tracer)
        self.free_slots = Channel("free_slots", self.b, self.tracer)

    def _make_cache(self) -> None:
        """Cache + compiled-primitive setup; PagedServeLoop overrides."""
        self.cache = self.bundle.cache_init(self.b, self.s_max)
        # encdec prefill takes (params, enc_out, cache, ...)
        cache_arg = 2 if self.cfg.family == "encdec" else 1
        self._fwd = _shared_jit(self.bundle.prefill, donate=(cache_arg,))
        self._reset = _shared_jit(self.bundle.cache_reset, donate=(0,))

    # -- shared step dispatch ------------------------------------------------

    def _step(self, tok: np.ndarray, n_valid: np.ndarray):
        st = self.stats
        with _span(st, "step.dispatch", "dispatch_s"):
            args = (jnp.asarray(tok, jnp.int32), jnp.asarray(self.pos),
                    jnp.asarray(n_valid, jnp.int32))
            if self.paged:
                args = args + (jnp.asarray(self.table),)
                logits, self.cache = self._fwd(self.params, self.cache,
                                               *args)
            elif self._encdec:
                logits, self.cache = self._fwd(self.params, self.enc_out,
                                               self.cache, *args)
            else:
                logits, self.cache = self._fwd(self.params, self.cache,
                                               *args)
        with _span(st, "step.wait", "wait_s"):
            jax.block_until_ready(logits)
        with _span(st, "step.copy", "copy_s"):
            out = np.asarray(logits)
        st.logits_bytes += out.nbytes
        return out

    def _dequeued(self, req: Request) -> None:
        """Count the wait of a request a slot took from admit_q."""
        self.stats.queue_wait_s += time.perf_counter() - req.t_queued
        self.stats.queue_waits += 1

    # -- Access engine: admission + chunked prefill --------------------------

    def _admit(self) -> None:
        with _span(self.stats, "serve.admit", "admit_s"):
            reset: List[int] = []
            while self.free_slots and self.admit_q:
                slot = self.free_slots.pop()
                req = self.admit_q.pop()
                self._dequeued(req)
                prompt = np.asarray(req.prompt, np.int64).reshape(-1)
                if prompt.size == 0:
                    # empty prompt: generate from an implicit BOS token
                    prompt = np.array([self.bos], np.int64)
                req.out = []
                self.active[slot] = req
                self._prompt[slot] = prompt
                self._psize[slot] = prompt.size
                self._ptr[slot] = 0
                self.pos[slot] = 0
                self.phase[slot] = _PREFILL
                self.stats.admitted += 1
                reset.append(slot)
            if reset:
                keep = np.ones(self.b, bool)
                keep[reset] = False
                self.cache = self._reset(self.cache, jnp.asarray(keep))
                if self._encdec:
                    self._encode_slots(reset)

    def _encode_slots(self, slots: List[int]) -> None:
        for slot in slots:
            req = self.active[slot]
            if req.frames is None:
                raise ValueError(f"request {req.rid}: encdec serving "
                                 "requires Request.frames")
            row = self._encode(self.params, jnp.asarray(req.frames)[None])
            if self.enc_out is None:
                # the per-slot encoder-output buffer (and hence the jit
                # signature of the decode/prefill step) is sized by the
                # first request; callers must pad frames to one fixed
                # encoder length per loop
                self.enc_out = jnp.zeros((self.b,) + row.shape[1:],
                                         row.dtype)
            elif row.shape[1:] != self.enc_out.shape[1:]:
                raise ValueError(
                    f"request {req.rid}: frames encode to {row.shape[1:]} "
                    f"but this loop's encoder buffer is "
                    f"{self.enc_out.shape[1:]}; pad all requests' frames "
                    "to one fixed encoder length per ServeLoop")
            self.enc_out = self.enc_out.at[slot].set(row[0])

    # paged-serving hooks (no-ops on the contiguous path) --------------------

    def _prefill_grant(self, slot: int, ptr: int, n: int) -> int:
        return n

    def _on_prompt_complete(self, slot: int) -> None:
        pass

    def _keep_logits(self, rid: int, row: np.ndarray) -> None:
        if self.token_logits is not None:
            self.token_logits.setdefault(rid, []).append(np.array(row))

    def _first_token(self, slot: int, logits: np.ndarray) -> int:
        req = self.active[slot]
        first = int(np.argmax(logits[slot]))
        req.out.append(first)
        self._keep_logits(req.rid, logits[slot])
        return first

    def _prefill_step(self, t0: float, results: Dict[int, List[int]]) -> None:
        st = self.stats
        with _span(st, "serve.prepare", "prepare_s"):
            slots = np.flatnonzero(self.phase == _PREFILL)
            if slots.size == 0:
                return
            tok = np.zeros((self.b, self.chunk), np.int64)
            n_valid = np.zeros(self.b, np.int64)
            for slot in slots:
                if self.phase[slot] != _PREFILL:  # preempted by a grant
                    continue
                prompt = self._prompt[slot]
                n = min(self.chunk, prompt.size - self._ptr[slot])
                n = self._prefill_grant(slot, int(self._ptr[slot]), int(n))
                if n > 0:
                    tok[slot, :n] = prompt[self._ptr[slot]:self._ptr[slot] + n]
                n_valid[slot] = n
            n_valid[self.phase != _PREFILL] = 0
            if not n_valid.any():
                return                          # everyone stalled on pages
        logits = self._step(tok, n_valid)
        with _span(st, "serve.emit", "emit_s"):
            st.prefill_steps += 1
            st.prefill_tokens += int(n_valid.sum())
            for slot in slots:
                if self.phase[slot] != _PREFILL:
                    continue
                self._ptr[slot] += n_valid[slot]
                self.pos[slot] += n_valid[slot]
                if self._ptr[slot] < self._prompt[slot].size:
                    continue
                # prompt complete: the chunk's last-valid logits are the
                # prediction after the final prompt token — the first
                # output token rides the handoff channel into the Execute
                # engine, which activates the slot when it pops the entry
                req = self.active[slot]
                self._on_prompt_complete(slot)
                if self.active[slot] is not req:
                    # the hook preempted/parked the slot (e.g. the sharded
                    # loop's prefill->decode page migration ran dry)
                    continue
                first = self._first_token(slot, logits)
                if req.rid not in st.ttft:      # resumes keep the original
                    st.ttft[req.rid] = (time.perf_counter() - t0
                                        - req.t_arrival)
                self.remaining[slot] = req.max_new - len(req.out)
                if first == self.eos or self.remaining[slot] <= 0:
                    self._finish(slot, results)
                else:
                    self.phase[slot] = _HANDOFF
                    self.handoff.push((slot, first))

    # -- Execute engine: dense masked decode ---------------------------------

    def _decode_mask(self) -> np.ndarray:
        return self.phase == _DECODE

    def _decode_step(self, results: Dict[int, List[int]]) -> None:
        st = self.stats
        with _span(st, "serve.prepare", "prepare_s"):
            # absorb freshly prefilled slots: the (slot, first token)
            # entry on the handoff channel is what activates decoding
            while self.handoff:
                slot, first = self.handoff.pop()
                self.cur[slot] = first
                self.phase[slot] = _DECODE
            active = self._decode_mask()
            if not active.any():
                return
            tok, n_valid = self.cur[:, None], active.astype(np.int64)
        logits = self._step(tok, n_valid)
        with _span(st, "serve.sample", "sample_s"):
            nxt = np.argmax(logits, axis=-1)
        with _span(st, "serve.emit", "emit_s"):
            st.decode_steps += 1
            st.decode_tokens += int(active.sum())
            for slot in np.flatnonzero(active):
                tok = int(nxt[slot])
                req = self.active[slot]
                req.out.append(tok)
                self._keep_logits(req.rid, logits[slot])
                self.cur[slot] = tok
                self.pos[slot] += 1
                self.remaining[slot] -= 1
                if tok == self.eos or self.remaining[slot] <= 0:
                    self._finish(slot, results)

    def _finish(self, slot: int, results: Dict[int, List[int]]) -> None:
        req = self.active[slot]
        results[req.rid] = req.out
        self.active[slot] = None
        self._prompt[slot] = None
        self.phase[slot] = _FREE
        self.free_slots.push(slot)

    # -- scheduler -----------------------------------------------------------

    def _reserved_tokens(self) -> int:
        res = 0
        for slot in range(self.b):
            req = self.active[slot]
            if req is not None:
                res += int(self._psize[slot]) + req.max_new
        return res

    def run(self, requests: List[Request], max_rounds: int = 100_000
            ) -> Dict[int, List[int]]:
        results: Dict[int, List[int]] = {}
        # validate everything up front: rejecting a request after some
        # of this batch was admitted would leave slots mid-flight
        _validate_requests(requests, self.s_max, self._encdec)
        t0 = time.perf_counter()
        pending = deque()
        for req in sorted(requests, key=lambda r: r.t_arrival):
            if req.max_new <= 0:
                results[req.rid] = []
            else:
                pending.append(req)
        rounds = 0
        while (pending or self._overflow or self.admit_q
               or (self.phase != _FREE).any()):
            rounds += 1
            self.stats.rounds = rounds
            if rounds > max_rounds:
                raise RuntimeError("serve loop exceeded max_rounds")
            # preempted/backlogged requests re-enter ahead of new arrivals
            while self._overflow and self.admit_q.push(self._overflow[0]):
                self._overflow.popleft()
            now = time.perf_counter() - t0
            while pending and pending[0].t_arrival <= now:
                req = pending.popleft()
                if not self.admit_q.push(req):
                    self._overflow.append(req)
            self._admit()
            self.stats.peak_reserved_tokens = max(
                self.stats.peak_reserved_tokens, self._reserved_tokens())
            self._decode_step(results)
            self._prefill_step(t0, results)
            if (pending and not self.admit_q and not self._overflow
                    and not (self.phase != _FREE).any()):
                wait = pending[0].t_arrival - (time.perf_counter() - t0)
                if wait > 0:                 # open-loop idle: sleep to arrival
                    time.sleep(min(wait, 0.05))
        return results


class PagedServeLoop(ServeLoop):
    """The serve pipeline on paged KV (see module docstring).

    ``page`` is the tokens-per-page granularity; ``n_pages`` the
    physical pool size (default: page 0 plus exactly ``batch_slots``
    full horizons, i.e. capacity-equivalent to the contiguous cache —
    pass less to oversubscribe); ``low_water`` parks admission while
    fewer than that many pages stay free for the decode stream;
    ``prefix_reuse=False`` disables the prefix cache.  For bundles
    without paged primitives (recurrent families, encdec) every
    override defers to the contiguous base-class path.
    """

    def __init__(self, cfg, bundle, params, batch_slots: int, s_max: int,
                 eos_id: int = -1, chunk: int = 32, bos_id: int = 0,
                 tracer: Optional[Tracer] = None,
                 admit_capacity: Optional[int] = None,
                 page: int = 16, n_pages: Optional[int] = None,
                 low_water: int = 0, prefix_reuse: bool = True,
                 record_logits: bool = False):
        self.page = page
        self._n_pages_arg = n_pages
        self.low_water = low_water
        self._prefix_reuse = prefix_reuse
        super().__init__(cfg, bundle, params, batch_slots, s_max,
                         eos_id=eos_id, chunk=chunk, bos_id=bos_id,
                         tracer=tracer, admit_capacity=admit_capacity,
                         record_logits=record_logits)

    def _make_cache(self) -> None:
        bundle = self.bundle
        self.paged = bundle.cache_init_paged is not None
        if not self.paged:
            super()._make_cache()       # dense fallback (recurrent state)
            return
        if self.page < 1:
            raise ValueError("page must be >= 1")
        self.npb = -(-self.s_max // self.page)      # blocks per slot horizon
        n_pages = self._n_pages_arg
        if n_pages is None:
            n_pages = 1 + self.b * self.npb
        if n_pages < 1 + self.npb:
            raise ValueError(
                f"n_pages ({n_pages}) must cover the trash page plus one "
                f"full horizon ({self.npb} pages) or no request can finish")
        self.n_pages = n_pages
        self.alloc = PageAllocator(n_pages, self.page)
        self.table = np.zeros((self.b, self.npb), np.int32)   # 0 = trash page
        self.n_blocks = np.zeros(self.b, np.int64)
        self.prefix = PrefixCache() if self._prefix_reuse else None
        self._slot_seq = np.zeros(self.b, np.int64)
        self._seq = 0
        self._resume_out: Dict[int, List[int]] = {}
        self._is_resume = np.zeros(self.b, bool)
        self.cache = self._new_pool(n_pages)
        self._fwd = _shared_jit(bundle.prefill_paged, donate=(1,))
        self._reset_paged = _shared_jit(bundle.cache_reset_paged,
                                        donate=(0,))
        self._copy = _shared_jit(bundle.copy_pages, donate=(0,))

    def _new_pool(self, n_pages: int):
        """A zeroed ``n_pages`` page pool; the sharded loop builds it in
        place on its decode mesh."""
        return self.bundle.cache_init_paged(self.b, n_pages, self.page)

    # -- page machinery ------------------------------------------------------

    def _reclaim(self, need_free: int) -> None:
        """Evict prefix-cache entries LRU-first until ``need_free``
        pages are free (or the cache is empty)."""
        while self.alloc.free_count < need_free:
            if self.prefix is None or not self.prefix.evict_lru(self.alloc):
                return

    def _pick_victim(self, requester: int) -> Optional[int]:
        """Strictly-younger victim (so the oldest slot always makes
        progress — no livelock), preferring decode-phase slots (they
        hold the most pages), youngest first."""
        my_seq = self._slot_seq[requester]
        pref_rank = {_DECODE: 2, _HANDOFF: 1, _PREFILL: 0}
        best, best_key = None, None
        for s in range(self.b):
            if s == requester or self.phase[s] == _FREE:
                continue
            if self._slot_seq[s] <= my_seq:
                continue
            key = (pref_rank[int(self.phase[s])], int(self._slot_seq[s]))
            if best_key is None or key > best_key:
                best, best_key = s, key
        return best

    def _preempt(self, victim: int) -> None:
        """Recompute-style preemption: release the victim's pages and
        park its request (with generated-so-far tokens) back on the
        admit queue; on re-admission the prefill teacher-forces
        prompt + out[:-1], so outputs are bit-identical."""
        req = self.active[victim]
        self._resume_out[req.rid] = req.out if req.out is not None else []
        # drop any pending handoff entry for this slot (pop/push cycle
        # keeps the tracer's occupancy record consistent)
        for _ in range(len(self.handoff)):
            entry = self.handoff.pop()
            if entry[0] != victim:
                self.handoff.push(entry)
        for i in range(int(self.n_blocks[victim])):
            self.alloc.decref(int(self.table[victim, i]))
            self.table[victim, i] = 0
        self.n_blocks[victim] = 0
        self.active[victim] = None
        self._prompt[victim] = None
        self.phase[victim] = _FREE
        self._is_resume[victim] = False
        self.free_slots.push(victim)
        if not self.admit_q.push(req):
            self._overflow.append(req)
        self.stats.preemptions += 1

    def _alloc_page(self, requester: int) -> Optional[int]:
        """Allocate one page for ``requester``, escalating: free list ->
        prefix-cache eviction -> preempt a strictly-younger slot.
        Returns None only when the requester is the youngest holder —
        it then stalls for the round and retries."""
        while True:
            pg = self.alloc.alloc()
            if pg is not None:
                self.stats.page_allocs += 1
                return pg
            if self.prefix is not None and self.prefix.evict_lru(self.alloc):
                continue
            victim = self._pick_victim(requester)
            if victim is None:
                return None
            self._preempt(victim)

    # -- Access engine overrides ---------------------------------------------

    def _admit(self) -> None:
        if not self.paged:
            return super()._admit()
        with _span(self.stats, "serve.admit", "admit_s"):
            reset: List[int] = []
            new_lens = np.zeros(self.b, np.int64)
            while self.free_slots and self.admit_q:
                req = self.admit_q.peek()
                prompt = np.asarray(req.prompt, np.int64).reshape(-1)
                if prompt.size == 0:
                    prompt = np.array([self.bos], np.int64)
                resume = self._resume_out.get(req.rid)
                if resume:
                    # teacher-force the tokens generated before preemption;
                    # the last one re-enters decode via the handoff channel
                    fill = np.concatenate(
                        [prompt, np.asarray(resume[:-1], np.int64)])
                else:
                    fill = prompt
                matched, pages = 0, []
                if self.prefix is not None:
                    # at least one token must actually prefill (its logits
                    # seed the first output), hence the size-1 cap
                    matched, pages = self.prefix.lookup(
                        fill, fill.size - 1, self.alloc)
                total_blocks = -(-fill.size // self.page)
                # a shared partial tail page costs one extra page (COW copy)
                need = (total_blocks - len(pages)
                        + (1 if matched % self.page else 0))
                busy = (self.phase != _FREE).any()
                gate = need + (self.low_water if busy else 0)
                if self.alloc.free_count < gate:
                    self._reclaim(gate)
                if self.alloc.free_count < gate:
                    for p in pages:             # park: head stays queued
                        self.alloc.decref(p)
                    break
                self._dequeued(self.admit_q.pop())
                slot = self.free_slots.pop()
                req.out = self._resume_out.pop(req.rid, None) or []
                self._is_resume[slot] = bool(req.out)
                self.active[slot] = req
                self._prompt[slot] = fill
                self._psize[slot] = prompt.size
                self.table[slot, :] = 0
                for i, p in enumerate(pages):
                    self.table[slot, i] = p
                self.n_blocks[slot] = len(pages)
                self._ptr[slot] = matched
                self.pos[slot] = matched
                self.phase[slot] = _PREFILL
                self._seq += 1
                self._slot_seq[slot] = self._seq
                self.stats.admitted += 1
                if matched:
                    self.stats.prefix_hits += 1
                    self.stats.prefix_tokens_reused += matched
                reset.append(slot)
                new_lens[slot] = matched
            if reset:
                keep = np.ones(self.b, bool)
                keep[reset] = False
                self._reset_slots(reset, keep, new_lens)

    def _reset_slots(self, reset, keep, new_lens) -> None:
        """Zero the cache lengths of freshly admitted slots; the sharded
        loop overrides to also reset its prefill staging pool."""
        self.cache = self._reset_paged(
            self.cache, jnp.asarray(keep),
            jnp.asarray(new_lens, jnp.int32))

    def _prefill_grant(self, slot: int, ptr: int, n: int) -> int:
        """Map pages under [ptr, ptr+n), copy-on-write if the write
        starts inside a shared page; returns how many of the n tokens
        are actually backed (0 = stall this round)."""
        if not self.paged or n <= 0:
            return n
        page = self.page
        if ptr % page:
            blk = ptr // page
            pg = int(self.table[slot, blk])
            if self.alloc.rc[pg] > 1:       # shared partial page: diverging
                fresh = self._alloc_page(slot)
                if fresh is None:
                    return 0
                self.cache = self._copy(self.cache,
                                        jnp.asarray(pg, jnp.int32),
                                        jnp.asarray(fresh, jnp.int32))
                self.alloc.decref(pg)
                self.table[slot, blk] = fresh
                self.stats.cow_copies += 1
        last_blk = (ptr + n - 1) // page
        while self.n_blocks[slot] <= last_blk:
            pg = self._alloc_page(slot)
            if pg is None:
                granted = int(self.n_blocks[slot]) * page - ptr
                return max(0, granted)
            self.table[slot, int(self.n_blocks[slot])] = pg
            self.n_blocks[slot] += 1
        return n

    def _on_prompt_complete(self, slot: int) -> None:
        if not self.paged or self.prefix is None:
            return
        fill = self._prompt[slot]
        page = self.page
        bounds = list(range(page, fill.size + 1, page))
        if fill.size % page:
            bounds.append(fill.size)
        for length in bounds:
            nb = -(-length // page)
            pages = [int(self.table[slot, i]) for i in range(nb)]
            self.prefix.register(fill, length, pages, self.alloc)

    def _first_token(self, slot: int, logits: np.ndarray) -> int:
        if self.paged and self._is_resume[slot]:
            self._is_resume[slot] = False
            return int(self.active[slot].out[-1])
        return super()._first_token(slot, logits)

    # -- Execute engine override ---------------------------------------------

    def _decode_mask(self) -> np.ndarray:
        if not self.paged:
            return super()._decode_mask()
        ready = np.ones(self.b, bool)
        for slot in np.flatnonzero(self.phase == _DECODE):
            if self.phase[slot] != _DECODE:     # preempted earlier this loop
                continue
            blk = int(self.pos[slot]) // self.page
            if blk >= self.n_blocks[slot]:
                pg = self._alloc_page(slot)
                if pg is None:
                    ready[slot] = False         # stall; retry next round
                    continue
                self.table[slot, blk] = pg
                self.n_blocks[slot] += 1
        return (self.phase == _DECODE) & ready

    def _finish(self, slot: int, results: Dict[int, List[int]]) -> None:
        if self.paged:
            for i in range(int(self.n_blocks[slot])):
                self.alloc.decref(int(self.table[slot, i]))
                self.table[slot, i] = 0
            self.n_blocks[slot] = 0
        super()._finish(slot, results)

    # -- introspection -------------------------------------------------------

    def page_stats(self) -> Dict[str, Any]:
        """Pool occupancy snapshot: fragmentation is the fraction of
        allocated page capacity not holding a live token (page-interior
        waste plus prefix-pinned pages)."""
        if not self.paged:
            return {"paged": False}
        used = self.n_pages - 1 - self.alloc.free_count
        committed = int(self.pos[self.phase != _FREE].sum())
        capacity = used * self.page
        return {"paged": True, "n_pages": self.n_pages, "page": self.page,
                "pages_used": used, "pages_free": self.alloc.free_count,
                "committed_tokens": committed,
                "capacity_tokens": capacity,
                "fragmentation": 1.0 - committed / capacity if capacity
                else 0.0,
                "prefix_entries": len(self.prefix) if self.prefix else 0}


class LegacyServeLoop:
    """The coupled pre-rewrite loop, kept as the serving baseline.

    Admission prefills one token at a time through the FULL-BATCH decode
    step, so every active slot stalls for the whole prompt length (and
    has its KV cache polluted once per prompt token — the loop is only
    actually correct for one slot serving one request from a fresh
    cache).  ``benchmarks/serve_bench.py`` measures the decoupled loop
    against this one, and the parity tests pin bit-identical outputs on
    the cells where this loop is correct.
    """

    def __init__(self, cfg, bundle, params, batch_slots: int, s_max: int,
                 eos_id: int = -1, bos_id: int = 0):
        self.cfg = cfg
        self.bundle = bundle
        self.params = params
        self.b = batch_slots
        self.s_max = s_max
        self.eos = eos_id
        self.bos = bos_id
        self.cache = bundle.cache_init(batch_slots, s_max)
        self.pos = jnp.zeros((batch_slots,), jnp.int32)
        self.cur = jnp.zeros((batch_slots,), jnp.int32)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.remaining = np.zeros(batch_slots, np.int64)
        self._step = _shared_jit(bundle.decode_step)

    def _admit(self, queue: List[Request],
               results: Dict[int, List[int]]) -> None:
        for slot in range(self.b):
            if self.active[slot] is None and queue:
                req = queue.pop(0)
                req.out = []
                self.active[slot] = req
                prompt = np.asarray(req.prompt, np.int64).reshape(-1)
                if prompt.size == 0:
                    # empty prompt: generate from an implicit BOS token
                    # (without this, no prefill iteration ran and
                    # ``logits`` below was unbound)
                    prompt = np.array([self.bos], np.int64)
                # prefill: feed prompt tokens through the decode step
                pos = 0
                for tok in prompt:
                    logits, self.cache = self._step(
                        self.params, self.cache,
                        self.cur.at[slot].set(int(tok)),
                        self.pos.at[slot].set(pos))
                    pos += 1
                first = int(jnp.argmax(logits[slot]))
                req.out.append(first)          # prefill's own prediction
                self.pos = self.pos.at[slot].set(pos)
                self.cur = self.cur.at[slot].set(first)
                self.remaining[slot] = req.max_new - 1
                if first == self.eos or self.remaining[slot] <= 0:
                    results[req.rid] = req.out
                    self.active[slot] = None

    def run(self, requests: List[Request], max_rounds: int = 10_000
            ) -> Dict[int, List[int]]:
        # same up-front validation as the decoupled loop: without it,
        # oversized prompts silently scattered past s_max into the cache
        _validate_requests(requests, self.s_max)
        queue = []
        results: Dict[int, List[int]] = {}
        for req in requests:
            if req.max_new <= 0:
                results[req.rid] = []
                continue
            queue.append(req)
        rounds = 0
        while (queue or any(a is not None for a in self.active)):
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("serve loop exceeded max_rounds")
            self._admit(queue, results)
            if not any(a is not None for a in self.active):
                continue
            logits, self.cache = self._step(self.params, self.cache,
                                            self.cur, self.pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.pos = self.pos + jnp.asarray(
                [a is not None for a in self.active], jnp.int32)
            self.cur = nxt
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                tok = int(nxt[slot])
                req.out.append(tok)
                self.remaining[slot] -= 1
                if tok == self.eos or self.remaining[slot] <= 0:
                    results[req.rid] = req.out
                    self.active[slot] = None
        return results
