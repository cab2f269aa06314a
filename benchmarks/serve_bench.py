"""Serving benchmark: paged-KV decoupled pipeline, open-loop arrival
traces, and the legacy-loop comparison sweep.

Two consumers:

  * ``python -m benchmarks.run serve`` — the CSV sweep: decoupled
    Access/Execute loop vs the coupled legacy loop across batch_slots x
    prompt mixes x model archetypes, plus the paged open-loop cells;
  * ``cells(ctx)`` — the ``serve`` axis of the benchmark matrix
    (schema-v2 ``BENCH_serve.json``, gated by ``benchmarks.diff``).

The matrix cells are the load-bearing ones:

  * ``serve/open/{poisson,bursty}/paged/s64`` — slots=64 under a seeded
    open-loop arrival trace (Poisson / bursty) of prompts sharing a
    page-aligned system prefix.  The Poisson cell runs the *same trace*
    through the contiguous loop and asserts the paged loop (a) returns
    bit-identical outputs, (b) sustains >= the contiguous tokens/s, and
    (c) admitted a concurrent reservation footprint
    (sum of prompt+max_new over live slots) larger than its physical
    page pool — the oversubscription a contiguous reservation allocator
    cannot express.  TTFT p50/p95/p99 are measured from each request's
    arrival via the shared linear-interpolated percentile helper
    (``repro.bench.percentiles``).
  * ``serve/parity/<arch>`` — closed-loop paged-vs-contiguous greedy
    bit-parity per attention family (GQA dense, MoE, MLA) and the
    recurrent fallback (rwkv, where ``PagedServeLoop`` must detect the
    missing paged primitives and serve contiguously).
  * ``serve/prefix/qwen3-4b`` — the same prompt served twice: the
    second run must return identical tokens with strictly fewer page
    allocations (prefix adoption).

Determinism discipline: integer ``derived`` values (request/token
counts, prefix hits, page allocations) are exact-diffed against the
committed baseline, so every int reported here is structural —
timing-dependent measurements (tokens/s, TTFT quantiles) are floats,
which the diff treats as informational.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path
from typing import List

import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[1]

MIXES = {
    "short": (6, 6),       # uniform short prompts
    "long": (40, 48),      # uniform long prompts
    "mixed": (4, 48),      # alternating short/long — the stall workload
}
ARCHS = ("qwen3-4b", "granite-moe-3b-a800m", "rwkv6-1.6b", "hymba-1.5b")
SLOTS = (2, 8)
SMOKE_ARCHS = ("qwen3-4b",)
SMOKE_SLOTS = (8,)
SMOKE_MIXES = ("mixed",)
GATE_SPEEDUP = 5.0         # slots=8 mixed cell: decoupled >= 5x legacy
MAX_NEW = 16
N_REQUESTS = 12
CHUNK = 16

# paged open-loop cells (slots >= 64 is the ROADMAP's serving regime)
OPEN_SLOTS = 64
OPEN_N = 64                # trace length
PAGE = 8
S_LOG = 96                 # per-slot logical horizon (s_max)
S_PHYS = 48                # physical pool: OPEN_SLOTS * S_PHYS tokens
PREFIX_LEN = 64            # shared system prefix (page-aligned)
TAIL_LEN = 4               # unique per-request tail
OPEN_MAX_NEW = 8
# parity cells cover every attention family plus the recurrent fallback
PARITY_ARCHS = ("qwen3-4b", "granite-moe-3b-a800m", "minicpm3-4b",
                "rwkv6-1.6b")


def _prompts(mix: str, n: int, vocab: int, seed: int = 0):
    lo, hi = MIXES[mix]
    rng = np.random.default_rng(seed)
    lens = [lo if i % 2 == 0 else hi for i in range(n)]
    return [rng.integers(0, vocab, size=p) for p in lens]


def _requests(mix: str, vocab: int):
    from repro.runtime.serve_loop import Request
    return [Request(rid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(_prompts(mix, N_REQUESTS, vocab))]


def poisson_arrivals(n: int, mean_gap_s: float, rng) -> List[float]:
    """Seeded open-loop Poisson process: exponential interarrivals."""
    return list(np.cumsum(rng.exponential(mean_gap_s, size=n)))


def bursty_arrivals(n: int, burst: int, gap_s: float, rng) -> List[float]:
    """Bursts of ``burst`` near-simultaneous arrivals, ``gap_s`` apart
    (with ~0.1ms in-burst jitter so arrival order is still seeded)."""
    out = []
    for i in range(n):
        out.append((i // burst) * gap_s + rng.uniform(0, 1e-4))
    return sorted(out)


def _occ_summary(trace) -> str:
    occ = trace.channel_occupancy()
    return ",".join(f"{name.rsplit('/', 1)[-1]}:{mean:.1f}/{mx}"
                    for name, (mean, mx) in sorted(occ.items()))


def _model(arch):
    import jax

    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def _bench_cell(cfg, bundle, params, mix, slots, s_max):
    from repro.bench import percentiles
    from repro.core.trace import Tracer
    from repro.runtime.serve_loop import LegacyServeLoop, Request, ServeLoop

    def warm():
        return [Request(rid=-1, prompt=np.array([1, 2], np.int64),
                        max_new=2)]

    # compile on a throwaway loop (the jit caches are shared per bundle
    # function), then measure a FRESH loop so the tracer and stats see
    # only workload traffic
    ServeLoop(cfg, bundle, params, batch_slots=slots, s_max=s_max,
              chunk=CHUNK).run(warm())
    tracer = Tracer()
    loop = ServeLoop(cfg, bundle, params, batch_slots=slots, s_max=s_max,
                     chunk=CHUNK, tracer=tracer)
    reqs = _requests(mix, cfg.vocab)
    t0 = time.perf_counter()
    results = loop.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in results.values())
    ttft = [loop.stats.ttft[r.rid] for r in reqs]
    pct = percentiles(ttft, (50.0, 95.0, 99.0))

    LegacyServeLoop(cfg, bundle, params, batch_slots=slots,
                    s_max=s_max).run(warm())
    legacy = LegacyServeLoop(cfg, bundle, params, batch_slots=slots,
                             s_max=s_max)
    reqs_l = _requests(mix, cfg.vocab)
    t0 = time.perf_counter()
    results_l = legacy.run(reqs_l)
    dt_l = time.perf_counter() - t0
    toks_l = sum(len(v) for v in results_l.values())

    return {
        "tok_s": toks / dt,
        "legacy_tok_s": toks_l / dt_l,
        "speedup": (toks / dt) / (toks_l / dt_l),
        "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft),
        "ttft_p95_ms": 1e3 * pct["p95"],
        "occ": _occ_summary(tracer.summary()),
    }


def _parity_cell(cfg, bundle, params, s_max) -> None:
    """One slot, one request: legacy is correct here, so greedy outputs
    must be bit-identical between the loops."""
    from repro.runtime.serve_loop import LegacyServeLoop, Request, ServeLoop

    prompt = np.asarray(_prompts("mixed", 2, cfg.vocab, seed=7)[1])
    new = ServeLoop(cfg, bundle, params, batch_slots=1, s_max=s_max,
                    chunk=CHUNK)
    out_new = new.run([Request(rid=0, prompt=prompt, max_new=8)])[0]
    leg = LegacyServeLoop(cfg, bundle, params, batch_slots=1, s_max=s_max)
    out_leg = leg.run([Request(rid=0, prompt=prompt, max_new=8)])[0]
    if out_new != out_leg:  # must fire even under python -O
        raise AssertionError(
            f"{cfg.arch}: decoupled {out_new} != legacy {out_leg}")


# ---------------------------------------------------------------------------
# Paged open-loop cells
# ---------------------------------------------------------------------------


def _open_trace(vocab: int, arrivals: List[float], rng):
    """Shared system prefix + unique tails — the prefix-cache workload."""
    from repro.runtime.serve_loop import Request

    prefix = rng.integers(0, vocab, size=PREFIX_LEN)
    reqs = []
    for i, t in enumerate(arrivals):
        tail = rng.integers(0, vocab, size=TAIL_LEN)
        reqs.append(Request(rid=i, prompt=np.concatenate([prefix, tail]),
                            max_new=OPEN_MAX_NEW, t_arrival=float(t)))
    return prefix, reqs


def _clone(reqs):
    from repro.runtime.serve_loop import Request
    return [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                    t_arrival=r.t_arrival) for r in reqs]


def open_loop_cell(trace: str, seed: int = 0, compare: bool = True) -> dict:
    """Run the slots>=64 open-loop paged cell; ``compare`` also runs the
    contiguous loop on the same trace and enforces the gates."""
    from repro.bench import percentiles
    from repro.runtime.serve_loop import PagedServeLoop, Request, ServeLoop

    cfg, bundle, params = _model("qwen3-4b")
    rng = np.random.default_rng(seed)
    if trace == "poisson":
        arrivals = poisson_arrivals(OPEN_N, 2e-3, rng)
    elif trace == "bursty":
        arrivals = bursty_arrivals(OPEN_N, OPEN_SLOTS // 4, 0.1, rng)
    else:
        raise ValueError(f"unknown trace {trace!r}")
    prefix, reqs = _open_trace(cfg.vocab, arrivals, rng)
    n_pages = 1 + OPEN_SLOTS * S_PHYS // PAGE
    pool_tokens = (n_pages - 1) * PAGE

    paged = PagedServeLoop(cfg, bundle, params, batch_slots=OPEN_SLOTS,
                           s_max=S_LOG, chunk=CHUNK, page=PAGE,
                           n_pages=n_pages)
    # the warmup request is the system prompt itself: it compiles the
    # primitives AND registers the shared prefix, so every trace request
    # adopts it (prefill skips PREFIX_LEN of its PREFIX_LEN+TAIL tokens)
    paged.run([Request(rid=-1, prompt=prefix, max_new=OPEN_MAX_NEW)])
    base = paged.stats
    snap = (base.page_allocs, base.prefix_hits, base.prefix_tokens_reused,
            base.cow_copies, base.preemptions)
    t0 = time.perf_counter()
    res = paged.run(_clone(reqs))
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in res.values())
    ttft = [paged.stats.ttft[r.rid] for r in reqs]
    pct = percentiles(ttft, (50.0, 95.0, 99.0))
    pstats = paged.page_stats()
    cell = {
        "requests": len(reqs),
        "tokens": int(toks),
        "prefix_hits": base.prefix_hits - snap[1],
        "prefix_tokens_reused": base.prefix_tokens_reused - snap[2],
        "page_allocs": base.page_allocs - snap[0],
        "cow_copies": base.cow_copies - snap[3],
        "preemptions": base.preemptions - snap[4],
        "pinned_pages": int(pstats["pages_used"]),
        "tok_s": toks / dt,
        "ttft_p50_ms": 1e3 * pct["p50"],
        "ttft_p95_ms": 1e3 * pct["p95"],
        "ttft_p99_ms": 1e3 * pct["p99"],
        "peak_reserved_tokens": int(paged.stats.peak_reserved_tokens),
        "pool_tokens": pool_tokens,
        "dt_s": dt,
    }
    if not compare:
        return cell

    contig = ServeLoop(cfg, bundle, params, batch_slots=OPEN_SLOTS,
                       s_max=S_LOG, chunk=CHUNK)
    contig.run([Request(rid=-1, prompt=prefix, max_new=OPEN_MAX_NEW)])
    t0 = time.perf_counter()
    res_c = contig.run(_clone(reqs))
    dt_c = time.perf_counter() - t0
    toks_c = sum(len(v) for v in res_c.values())
    cell["contig_tok_s"] = toks_c / dt_c
    cell["speedup"] = cell["tok_s"] / cell["contig_tok_s"]
    # gates (must fire even under python -O)
    if res != res_c:
        raise AssertionError("open-loop paged outputs != contiguous")
    # static oversubscription witness (timing-independent, unlike the
    # peak_reserved_tokens sample): every request needs more KV than the
    # per-slot share of the physical pool, so a contiguous allocator
    # with the same memory (s_max = S_PHYS) could not admit ANY of them
    need = PREFIX_LEN + TAIL_LEN + OPEN_MAX_NEW
    if need <= pool_tokens // OPEN_SLOTS:
        raise AssertionError(
            f"trace does not oversubscribe: per-request KV {need} fits "
            f"the per-slot physical share {pool_tokens // OPEN_SLOTS}")
    if cell["speedup"] < 1.0:
        raise AssertionError(
            f"paged {cell['tok_s']:.1f} tok/s < contiguous "
            f"{cell['contig_tok_s']:.1f} tok/s")
    return cell


def paged_parity(arch: str, seed: int = 0) -> dict:
    """Closed-loop paged-vs-contiguous greedy bit-parity for one arch."""
    from repro.runtime.serve_loop import PagedServeLoop, Request, ServeLoop

    cfg, bundle, params = _model(arch)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=n)
               for n in (12, 3, 25, 7, 1, 18)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(prompts)]

    contig = ServeLoop(cfg, bundle, params, batch_slots=4, s_max=40,
                       chunk=CHUNK)
    r_c = contig.run(reqs())
    paged = PagedServeLoop(cfg, bundle, params, batch_slots=4, s_max=40,
                           chunk=CHUNK, page=PAGE)
    r_p = paged.run(reqs())
    if r_p != r_c:  # must fire even under python -O
        raise AssertionError(f"{arch}: paged {r_p} != contiguous {r_c}")
    fallback = not paged.paged
    expected_fallback = bundle.cache_init_paged is None
    if fallback != expected_fallback:
        raise AssertionError(f"{arch}: fallback={fallback} but bundle "
                             f"paged primitives absent={expected_fallback}")
    return {"requests": len(prompts),
            "tokens": int(sum(len(v) for v in r_c.values())),
            "match": 1, "fallback": int(fallback),
            "page_allocs": paged.stats.page_allocs}


def prefix_reuse_cell(seed: int = 0) -> dict:
    """Same prompt twice: identical outputs, strictly fewer allocations
    the second time (prefix adoption)."""
    from repro.runtime.serve_loop import PagedServeLoop, Request

    cfg, bundle, params = _model("qwen3-4b")
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab, size=3 * PAGE + 2)
    loop = PagedServeLoop(cfg, bundle, params, batch_slots=2, s_max=64,
                          chunk=CHUNK, page=PAGE)
    cold = loop.run([Request(rid=0, prompt=prompt, max_new=8)])
    allocs_cold = loop.stats.page_allocs
    warmr = loop.run([Request(rid=1, prompt=prompt, max_new=8)])
    allocs_warm = loop.stats.page_allocs - allocs_cold
    if cold[0] != warmr[1]:  # must fire even under python -O
        raise AssertionError("prefix-reuse outputs diverge")
    if allocs_warm >= allocs_cold:
        raise AssertionError(
            f"prefix reuse saved nothing: {allocs_warm} >= {allocs_cold}")
    return {"allocs_cold": allocs_cold, "allocs_warm": allocs_warm,
            "prefix_hits": loop.stats.prefix_hits,
            "prefix_tokens_reused": loop.stats.prefix_tokens_reused,
            "match": 1}


# ---------------------------------------------------------------------------
# Sharded serving cells (runtime/mesh_serve.py)
# ---------------------------------------------------------------------------


def sharded_mesh1_cell(seed: int = 0) -> dict:
    """Single-device co-located placement: ShardedPagedServeLoop on
    mesh(n=1) must be bit-identical to PagedServeLoop — same outputs,
    same structural counters — with control messages riding the
    (degenerate, identity-permute) MeshChannel ring."""
    from repro.launch.mesh import make_serve_meshes
    from repro.runtime.mesh_serve import ShardedPagedServeLoop
    from repro.runtime.serve_loop import PagedServeLoop, Request

    cfg, bundle, params = _model("qwen3-4b")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=n)
               for n in (12, 3, 25, 7, 1, 18)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(prompts)]

    base = PagedServeLoop(cfg, bundle, params, batch_slots=4, s_max=40,
                          chunk=CHUNK, page=PAGE)
    r0 = base.run(reqs())
    sharded = ShardedPagedServeLoop(cfg, bundle, params, batch_slots=4,
                                    s_max=40, meshes=make_serve_meshes(1),
                                    chunk=CHUNK, page=PAGE)
    r1 = sharded.run(reqs())
    if r0 != r1:  # must fire even under python -O
        raise AssertionError(f"mesh1 sharded != single-host: {r1} vs {r0}")
    for k in ("prefill_tokens", "decode_tokens", "page_allocs",
              "cow_copies", "preemptions", "prefix_hits"):
        if getattr(base.stats, k) != getattr(sharded.stats, k):
            raise AssertionError(
                f"mesh1 counter {k}: sharded {getattr(sharded.stats, k)} "
                f"!= base {getattr(base.stats, k)}")
    return {"requests": len(prompts),
            "tokens": int(sum(len(v) for v in r0.values())),
            "match": 1, "page_allocs": sharded.stats.page_allocs,
            "migrations": sharded.stats.migrations}


# the mesh8 open-loop snippet runs in a subprocess so the cell is
# reproducible from any parent (normal CI sees 1 device, the
# multi-device job 8 — the child always forces 8)
_MESH8_SNIPPET = """
    import json, time
    import jax, numpy as np
    assert jax.device_count() == 8, jax.device_count()
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.launch.mesh import make_serve_meshes
    from repro.runtime.serve_loop import PagedServeLoop, Request
    from repro.runtime.mesh_serve import ShardedPagedServeLoop

    seed = %d
    cfg = get_config("qwen3-4b", smoke=True)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    sizes = (12, 3, 25, 7, 1, 18, 9, 30)
    arrivals = np.cumsum(rng.exponential(2e-3, size=len(sizes)))
    prompts = [rng.integers(0, cfg.vocab, size=n) for n in sizes]
    def reqs():
        return [Request(rid=i, prompt=p, max_new=6, t_arrival=float(t))
                for i, (p, t) in enumerate(zip(prompts, arrivals))]
    # ample slots/pool + prefix off: every structural counter below is
    # arrival-timing independent (no preemption, no prefix adoption)
    kw = dict(batch_slots=8, s_max=40, chunk=16, page=8,
              prefix_reuse=False)
    base = PagedServeLoop(cfg, bundle, params, **kw)
    r0 = base.run(reqs())
    meshes = make_serve_meshes(8)
    assert meshes.disaggregated
    kw.pop("prefix_reuse")
    sh = ShardedPagedServeLoop(cfg, bundle, params, meshes=meshes, **kw)
    t0 = time.perf_counter()
    r1 = sh.run(reqs())
    dt = time.perf_counter() - t0
    assert r0 == r1, "disaggregated open-loop outputs diverge"
    toks = sum(len(v) for v in r1.values())
    print(json.dumps({
        "requests": len(sizes), "tokens": int(toks), "match": 1,
        "migrations": sh.stats.migrations,
        "page_allocs": sh.stats.page_allocs,
        "preemptions": sh.stats.preemptions,
        "prefix_hits": sh.stats.prefix_hits,
        "tok_s": toks / dt, "dt_s": dt}))
"""


def sharded_open_mesh8_cell(seed: int = 0) -> dict:
    """Disaggregated open-loop serving on 8 forced host devices:
    prefill and decode engines on disjoint 4-device submeshes, joined
    by mesh channels, with page migration between the pools.  Outputs
    must match the single-host paged loop on the same arrival trace.
    The child is pinned to the CPU: its devices are forced host devices,
    and a chip held by this process could not be shared with it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH8_SNIPPET % seed)],
        capture_output=True, text=True, env=env, timeout=600)
    if out.returncode != 0:  # must fire even under python -O
        raise AssertionError(
            f"mesh8 subprocess failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Matrix axis
# ---------------------------------------------------------------------------

_FLOAT_KEYS = ("tok_s", "contig_tok_s", "speedup", "ttft_p50_ms",
               "ttft_p95_ms", "ttft_p99_ms", "dt_s",
               # a wall-clock *sample* of concurrency, not structural:
               # how many arrivals overlap depends on machine speed
               "peak_reserved_tokens")


def _derived(cell: dict) -> dict:
    """Ints exact-diff; floats informational (see module docstring)."""
    out = {}
    for key, val in cell.items():
        out[key] = round(float(val), 3) if key in _FLOAT_KEYS else int(val)
    return out


def cells(ctx) -> List:
    """The ``serve`` axis of the benchmark matrix."""
    from repro.bench import Cell, CellResult, coords

    out: List = []

    def open_cell(trace, compare):
        def run(c) -> CellResult:
            t0 = time.perf_counter()
            cell = open_loop_cell(trace, seed=c.seed, compare=compare)
            us = (time.perf_counter() - t0) * 1e6
            return CellResult(us_warm=us, derived=_derived(cell))
        return run

    for trace, compare in (("poisson", True), ("bursty", False)):
        out.append(Cell(
            axis="serve", name=f"serve/open/{trace}/paged/s{OPEN_SLOTS}",
            coords=coords(f"serve-open-{trace}", "serve", engine="event",
                          backend="xla", tenants=OPEN_SLOTS),
            run=open_cell(trace, compare), group="serve-open"))

    def parity_run(arch):
        def run(c) -> CellResult:
            return CellResult(derived=_derived(paged_parity(arch,
                                                            seed=c.seed)))
        return run

    for arch in PARITY_ARCHS:
        out.append(Cell(
            axis="serve", name=f"serve/parity/{arch}/paged-vs-contig",
            coords=coords(f"serve-parity-{arch}", "serve", backend="xla",
                          tenants=4),
            run=parity_run(arch), group="serve-parity"))

    def prefix_run(c) -> CellResult:
        return CellResult(derived=_derived(prefix_reuse_cell(seed=c.seed)))

    out.append(Cell(
        axis="serve", name="serve/prefix/qwen3-4b/reuse",
        coords=coords("serve-prefix", "serve", backend="xla", tenants=2),
        run=prefix_run, group="serve-prefix"))

    def mesh1_run(c) -> CellResult:
        return CellResult(derived=_derived(sharded_mesh1_cell(seed=c.seed)))

    out.append(Cell(
        axis="serve", name="serve/sharded/mesh1/qwen3-4b/paged",
        coords=coords("serve-sharded-mesh1", "serve", backend="xla",
                      tenants=4),
        run=mesh1_run, group="serve-sharded"))

    def mesh8_run(c) -> CellResult:
        t0 = time.perf_counter()
        cell = sharded_open_mesh8_cell(seed=c.seed)
        us = (time.perf_counter() - t0) * 1e6
        return CellResult(us_warm=us, derived=_derived(cell))

    out.append(Cell(
        axis="serve", name="serve/sharded/open/mesh8/qwen3-4b/disagg",
        coords=coords("serve-sharded-mesh8", "serve", backend="xla",
                      tenants=8),
        run=mesh8_run, group="serve-sharded"))
    return out


# ---------------------------------------------------------------------------
# CLI sweep
# ---------------------------------------------------------------------------


def run(csv_print, smoke: bool = False) -> dict:
    archs = SMOKE_ARCHS if smoke else ARCHS
    slots_sweep = SMOKE_SLOTS if smoke else SLOTS
    mixes = SMOKE_MIXES if smoke else tuple(MIXES)
    s_max = max(hi for _, hi in MIXES.values()) + MAX_NEW + 8

    results = {}
    for arch in archs:
        cfg, bundle, params = _model(arch)
        _parity_cell(cfg, bundle, params, s_max)
        for mix in mixes:
            for slots in slots_sweep:
                cell = _bench_cell(cfg, bundle, params, mix, slots, s_max)
                results[(arch, mix, slots)] = cell
                csv_print(
                    f"serve/{arch}/{mix}/s{slots},{1e6 / cell['tok_s']:.1f},"
                    f"tok_s={cell['tok_s']:.1f};"
                    f"legacy={cell['legacy_tok_s']:.1f};"
                    f"speedup={cell['speedup']:.2f};"
                    f"ttft_ms={cell['ttft_mean_ms']:.0f}/"
                    f"{cell['ttft_p95_ms']:.0f};"
                    f"occ={cell['occ']}")
                if mix == "mixed" and slots == 8 and \
                        cell["speedup"] < GATE_SPEEDUP:
                    raise AssertionError(
                        f"{arch} mixed/s8: decoupled speedup "
                        f"{cell['speedup']:.2f}x < {GATE_SPEEDUP}x gate")
    # paged open-loop cells (the ROADMAP's slots>=64 serving regime)
    for trace in ("poisson",) if smoke else ("poisson", "bursty"):
        cell = open_loop_cell(trace, compare=(trace == "poisson"))
        results[("paged", trace, OPEN_SLOTS)] = cell
        extra = (f";vs_contig={cell['speedup']:.2f}x"
                 if "speedup" in cell else "")
        csv_print(
            f"serve/open/{trace}/paged/s{OPEN_SLOTS},"
            f"{1e6 / cell['tok_s']:.1f},"
            f"tok_s={cell['tok_s']:.1f};"
            f"ttft_ms={cell['ttft_p50_ms']:.0f}/{cell['ttft_p95_ms']:.0f}/"
            f"{cell['ttft_p99_ms']:.0f};"
            f"hits={cell['prefix_hits']}/{cell['requests']};"
            f"reserved={cell['peak_reserved_tokens']}"
            f"/{cell['pool_tokens']}{extra}")
    return results
