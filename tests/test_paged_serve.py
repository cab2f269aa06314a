"""Paged-KV serving: allocator/prefix-cache units, paged-vs-contiguous
bit-parity per attention family, page exhaustion -> preemption ->
completion, prefix reuse + copy-on-write divergence, open-loop arrival
semantics (t_arrival TTFT), and the request-validation sweep both loops
now share (duplicate rids, s_max overflow)."""

import time

import jax
import numpy as np
import pytest

from repro.bench import percentile, percentiles
from repro.configs import get_config
from repro.models.registry import build_model
from repro.runtime.serve_loop import (LegacyServeLoop, PageAllocator,
                                      PagedServeLoop, Request, ServeLoop)

FAST_ARCH = "qwen3-4b"
# one arch per attention family the paged cache supports (GQA dense,
# MoE, MLA) plus the recurrent fallback
PAGED_ARCHS = ("qwen3-4b", "granite-moe-3b-a800m", "minicpm3-4b")
FALLBACK_ARCHS = ("rwkv6-1.6b", "hymba-1.5b")

_MODELS = {}


def _model(arch, **over):
    key = (arch, tuple(sorted(over.items())))
    if key not in _MODELS:
        cfg = get_config(arch, smoke=True, **over)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        _MODELS[key] = (cfg, m, params)
    return _MODELS[key]


def _prompt(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=n)


# -- allocator / percentile units ---------------------------------------------


def test_page_allocator_basics():
    a = PageAllocator(n_pages=4, page=8)
    assert a.free_count == 3            # page 0 is the pinned trash page
    p1, p2, p3 = a.alloc(), a.alloc(), a.alloc()
    assert sorted([p1, p2, p3]) == [1, 2, 3]
    assert a.alloc() is None            # exhausted, never raises
    a.incref(p2)
    a.decref(p2)
    assert a.free_count == 0            # still referenced by the incref
    a.decref(p2)
    assert a.free_count == 1 and a.alloc() == p2
    with pytest.raises(ValueError):
        PageAllocator(n_pages=1, page=8)


def test_percentile_linear_interpolation():
    xs = list(range(1, 11))             # 1..10
    assert percentile(xs, 0) == 1
    assert percentile(xs, 100) == 10
    assert percentile(xs, 50) == 5.5
    # the old biased index sorted(v)[int(.95*len)] returned the max for
    # n=10; the interpolated estimator must not
    assert percentile(xs, 95) == pytest.approx(9.55)
    assert percentile([7.0], 99) == 7.0
    assert set(percentiles(xs)) == {"p50", "p95", "p99"}
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile(xs, 101)


# -- paged vs contiguous bit-parity -------------------------------------------


def _parity(arch, expect_fallback):
    cfg, m, params = _model(arch)
    prompts = [_prompt(n, cfg.vocab, seed=n) for n in (1, 5, 9, 18, 3)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new=6)
                for i, p in enumerate(prompts)]

    contig = ServeLoop(cfg, m, params, batch_slots=2, s_max=32, chunk=4)
    r_c = contig.run(reqs())
    paged = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=32,
                           chunk=4, page=8)
    r_p = paged.run(reqs())
    assert r_p == r_c, arch
    assert paged.paged is (not expect_fallback)
    if expect_fallback:
        assert paged.stats.page_allocs == 0
    else:
        assert paged.stats.page_allocs > 0


def test_paged_matches_contiguous_gqa():
    _parity(FAST_ARCH, expect_fallback=False)


def test_paged_fallback_recurrent():
    """Families with recurrent state expose no paged primitives; the
    paged loop must detect that and serve contiguously, bit-identical."""
    _parity("rwkv6-1.6b", expect_fallback=True)


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(set(PAGED_ARCHS) - {FAST_ARCH}))
def test_paged_matches_contiguous_all_families(arch):
    _parity(arch, expect_fallback=False)


@pytest.mark.slow
def test_paged_fallback_hybrid():
    _parity("hymba-1.5b", expect_fallback=True)


@pytest.mark.slow
def test_paged_pallas_matches_ref_mode():
    """kernel_mode=pallas drives flash_decode_paged's ring gather over
    the scalar-prefetched page table (interpret mode on CPU); greedy
    outputs must match the ref-mode paged loop."""
    cfg_r, m_r, params = _model(FAST_ARCH)
    cfg_p, m_p, params_p = _model(FAST_ARCH, kernel_mode="pallas")
    prompt = _prompt(11, cfg_r.vocab, seed=3)
    ref = PagedServeLoop(cfg_r, m_r, params, batch_slots=1, s_max=32,
                         page=8).run([Request(rid=0, prompt=prompt,
                                              max_new=4)])[0]
    pal = PagedServeLoop(cfg_p, m_p, params_p, batch_slots=1, s_max=32,
                         page=8).run([Request(rid=0, prompt=prompt,
                                              max_new=4)])[0]
    assert pal == ref


def test_paged_chunk_step_pallas_matches_ref_mode():
    """One ``prefill_paged`` chunk step: the pallas-mode grouped chunk
    attention against ref mode's per-query oracle, over three slots —
    idle (n_valid 0), mid-prompt across a page boundary, fresh — on a
    pool filled with noise.  Logits agree to float32 rounding; the
    scatter is shared, so lengths and the first layer's pages are
    bit-identical (later layers' K/V inherit the rounding); the idle
    slot's pages and length are untouched."""
    cfg_r, m_r, params = _model(FAST_ARCH)
    _, m_p, _ = _model(FAST_ARCH, kernel_mode="pallas")
    b, c, page, npb = 3, 4, 8, 4
    rng = np.random.default_rng(7)
    pos = np.array([5, 6, 0], np.int32)            # 6..9 crosses page 1->2
    n_valid = np.array([0, 4, 3], np.int32)
    table = 1 + np.arange(b * npb, dtype=np.int32).reshape(b, npb)
    cache = m_r.cache_init_paged(b, 1 + b * npb, page)
    noise = lambda a: rng.standard_normal(a.shape).astype(a.dtype)
    cache = [{"attn": {"kp": noise(seg["attn"]["kp"]),
                       "vp": noise(seg["attn"]["vp"]),
                       "len": np.broadcast_to(pos, seg["attn"]["len"].shape)}}
             for seg in cache]
    tok = rng.integers(0, cfg_r.vocab, (b, c)).astype(np.int32)
    args = (tok, pos, n_valid, table)
    logits_r, new_r = jax.jit(m_r.prefill_paged)(params, cache, *args)
    logits_p, new_p = jax.jit(m_p.prefill_paged)(params, cache, *args)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_r),
                               rtol=1e-4, atol=1e-4)
    for old, r, p in zip(cache, new_r, new_p):
        np.testing.assert_array_equal(np.asarray(p["attn"]["len"]),
                                      np.asarray(r["attn"]["len"]))
        assert (np.asarray(p["attn"]["len"]) == pos + n_valid).all()
        for key in ("kp", "vp"):
            pr, pp = np.asarray(r["attn"][key]), np.asarray(p["attn"][key])
            np.testing.assert_array_equal(pp[0], pr[0])
            np.testing.assert_allclose(pp, pr, rtol=1e-4, atol=1e-4)
            idle = table[0]                        # slot 0's pages
            np.testing.assert_array_equal(pp[:, idle],
                                          old["attn"][key][:, idle])


# -- page pressure: preemption and recovery -----------------------------------


def test_page_exhaustion_preempts_and_completes():
    """Pool sized so one slot's decode growth must evict the younger
    slot's pages: the victim is preempted back to the admit queue, the
    older slot progresses (no deadlock), and every request still
    completes with outputs bit-identical to a generous pool."""
    cfg, m, params = _model(FAST_ARCH)
    reqs = lambda: [Request(rid=0, prompt=_prompt(10, cfg.vocab, seed=1),
                            max_new=6),
                    Request(rid=1, prompt=_prompt(6, cfg.vocab, seed=2),
                            max_new=6)]
    roomy = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=16,
                           page=4, prefix_reuse=False)
    ref = roomy.run(reqs())
    assert roomy.stats.preemptions == 0

    tight = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=16,
                           page=4, n_pages=6, prefix_reuse=False)
    out = tight.run(reqs())
    assert tight.stats.preemptions >= 1
    assert out == ref                   # resume is teacher-forced exact


def test_min_pool_serial_completion():
    """The floor pool (one slot's worth) can never hold two requests;
    the loop must degrade to serial service, not deadlock."""
    cfg, m, params = _model(FAST_ARCH)
    loop = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=16,
                          page=4, n_pages=5, prefix_reuse=False)
    results = loop.run([Request(rid=i, prompt=_prompt(8, cfg.vocab, seed=i),
                                max_new=6) for i in range(3)])
    assert set(results) == {0, 1, 2}
    assert all(len(v) == 6 for v in results.values())


def test_pool_too_small_rejected():
    cfg, m, params = _model(FAST_ARCH)
    with pytest.raises(ValueError, match="page"):
        PagedServeLoop(cfg, m, params, batch_slots=1, s_max=16, page=4,
                       n_pages=4)      # needs 1 trash + 4 blocks


# -- prefix reuse and copy-on-write -------------------------------------------


def test_prefix_reuse_fewer_allocs_same_tokens():
    cfg, m, params = _model(FAST_ARCH)
    prompt = _prompt(18, cfg.vocab, seed=4)
    loop = PagedServeLoop(cfg, m, params, batch_slots=1, s_max=32, page=8)
    cold = loop.run([Request(rid=0, prompt=prompt, max_new=5)])
    allocs_cold = loop.stats.page_allocs
    warm = loop.run([Request(rid=1, prompt=prompt, max_new=5)])
    assert warm[1] == cold[0]
    assert loop.stats.prefix_hits == 1
    assert loop.stats.prefix_tokens_reused >= 8
    assert loop.stats.page_allocs - allocs_cold < allocs_cold


def test_cow_on_divergence_inside_shared_page():
    """Two prompts extending a registered 18-token prefix (18 % 8 != 0)
    adopt its partial page; each must copy it before writing (COW), and
    the donor's pages must stay byte-clean for a later re-serve."""
    cfg, m, params = _model(FAST_ARCH)
    base = _prompt(18, cfg.vocab, seed=5)
    ext_b = np.concatenate([base, [7, 3]])
    ext_c = np.concatenate([base, [9]])

    loop = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=32, page=8)
    out_a = loop.run([Request(rid=0, prompt=base, max_new=4)])[0]
    res = loop.run([Request(rid=1, prompt=ext_b, max_new=4),
                    Request(rid=2, prompt=ext_c, max_new=4)])
    assert loop.stats.cow_copies >= 2
    assert loop.stats.prefix_hits >= 2

    # outputs match fresh loops with no sharing at all
    for rid, prompt in ((1, ext_b), (2, ext_c)):
        solo = PagedServeLoop(cfg, m, params, batch_slots=1, s_max=32,
                              page=8, prefix_reuse=False)
        assert res[rid] == solo.run([Request(rid=0, prompt=prompt,
                                             max_new=4)])[0], rid
    # the shared partial page was not polluted by either adopter
    assert loop.run([Request(rid=3, prompt=base, max_new=4)])[3] == out_a


def test_page_stats_accounting():
    cfg, m, params = _model(FAST_ARCH)
    loop = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=32, page=8)
    loop.run([Request(rid=0, prompt=_prompt(12, cfg.vocab, seed=6),
                      max_new=4)])
    st = loop.page_stats()
    assert st["capacity_tokens"] == st["pages_used"] * 8
    assert 0 <= st["pages_used"] <= loop.alloc.n_pages - 1
    assert st["pages_used"] + st["pages_free"] == loop.alloc.n_pages - 1
    assert 0.0 <= st["fragmentation"] <= 1.0
    assert st["prefix_entries"] == len(loop.prefix)


# -- open-loop arrivals and TTFT ----------------------------------------------


def test_open_loop_arrivals_match_closed_loop():
    """Staggered t_arrival must change scheduling only, never outputs."""
    cfg, m, params = _model(FAST_ARCH)
    prompts = [_prompt(4 + i, cfg.vocab, seed=i) for i in range(4)]

    closed = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=32, page=8)
    ref = closed.run([Request(rid=i, prompt=p, max_new=4)
                      for i, p in enumerate(prompts)])
    opened = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=32, page=8)
    res = opened.run([Request(rid=i, prompt=p, max_new=4,
                              t_arrival=0.01 * i)
                      for i, p in enumerate(prompts)])
    assert res == ref
    assert set(opened.stats.ttft) == {0, 1, 2, 3}
    assert all(t >= 0.0 for t in opened.stats.ttft.values())


def test_ttft_measured_from_arrival_not_run_start():
    """A request arriving 50ms into the run must not have those 50ms
    billed to its TTFT (the old single-t0 bug billed queueing-before-
    arrival time that no client experienced)."""
    cfg, m, params = _model(FAST_ARCH)
    loop = ServeLoop(cfg, m, params, batch_slots=1, s_max=32)
    loop.run([Request(rid=0, prompt=_prompt(3, cfg.vocab), max_new=2)])

    delay = 0.05
    loop = ServeLoop(cfg, m, params, batch_slots=1, s_max=32)
    t0 = time.perf_counter()
    loop.run([Request(rid=0, prompt=_prompt(3, cfg.vocab), max_new=2,
                      t_arrival=delay)])
    total = time.perf_counter() - t0
    assert total >= delay               # the loop waited for the arrival
    assert loop.stats.ttft[0] <= total - delay + 0.01


# -- validation both loops share ----------------------------------------------


@pytest.mark.parametrize("cls", [ServeLoop, LegacyServeLoop,
                                 PagedServeLoop])
def test_duplicate_rid_rejected(cls):
    cfg, m, params = _model(FAST_ARCH)
    loop = cls(cfg, m, params, batch_slots=1, s_max=32)
    with pytest.raises(ValueError, match="duplicate"):
        loop.run([Request(rid=5, prompt=_prompt(3, cfg.vocab), max_new=2),
                  Request(rid=5, prompt=_prompt(4, cfg.vocab), max_new=2)])


@pytest.mark.parametrize("cls", [ServeLoop, LegacyServeLoop,
                                 PagedServeLoop])
def test_oversize_request_rejected(cls):
    """LegacyServeLoop used to skip this validation entirely and
    overflow the cache instead; all three loops now reject up front."""
    cfg, m, params = _model(FAST_ARCH)
    loop = cls(cfg, m, params, batch_slots=1, s_max=16)
    with pytest.raises(ValueError, match="s_max"):
        loop.run([Request(rid=0, prompt=_prompt(12, cfg.vocab),
                          max_new=8)])
