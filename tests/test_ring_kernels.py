"""Differential tests pinning every ring-emitter kernel against its
ref.py oracle in interpret mode.

Two tiers over the same check helpers:

* a deterministic edge-case grid that always runs (rif=1, rif > chunk /
  tile count, non-multiple tails, empty runs) — the regimes where the
  shared emitter's prologue/steady-state/drain structure degenerates;
* hypothesis sweeps over the case strategies in ``tests/strategies.py``
  (skipped when the optional ``hypothesis`` extra is missing, as in the
  fast local tier; CI installs it).

Plus dispatch-order tests for the chase and grouped-matmul ops:
explicit knob → tune-cache winner → ``plan_rif`` analytic seeding.
"""

import numpy as np
import pytest

import jax.numpy as jnp


def _np(x):
    return np.asarray(x, np.float32)


# ---------------------------------------------------------------------------
# Check helpers (shared by the deterministic grid and hypothesis sweeps)
# ---------------------------------------------------------------------------


def check_gather(case, seed=0, method="rif"):
    from repro.kernels.dae_gather import dae_gather, gather_ref
    r = np.random.default_rng(seed)
    dtype = jnp.dtype(case["dtype"])
    table = jnp.asarray(r.standard_normal((case["n"], case["d"])), dtype)
    idx = jnp.asarray(r.integers(0, case["n"], case["m"]), jnp.int32)
    out = dae_gather(table, idx, method=method, block_d=128,
                     chunk=case["chunk"], rif=case["rif"], interpret=True)
    np.testing.assert_array_equal(_np(out), _np(gather_ref(table, idx)))


def check_merge(case, seed=0):
    from repro.kernels.dae_merge import merge_ref, merge_sorted
    r = np.random.default_rng(seed)
    n, m = case["n"], case["m"]
    dtype = jnp.dtype(case["dtype"])
    if dtype == jnp.int32:
        a = jnp.sort(jnp.asarray(r.integers(0, 40, max(n, 1))[:n], dtype))
        b = jnp.sort(jnp.asarray(r.integers(0, 40, max(m, 1))[:m], dtype))
    else:
        a = jnp.sort(jnp.asarray(r.standard_normal(max(n, 1))[:n], dtype))
        b = jnp.sort(jnp.asarray(r.standard_normal(max(m, 1))[:m], dtype))
    out = merge_sorted(a, b, tile=case["tile"], rif=case["rif"],
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(merge_ref(a, b)))


def check_spmv(case, seed=0):
    from repro.kernels.dae_spmv import csr_to_bsr, dae_spmv, spmv_ref
    r = np.random.default_rng(seed)
    nrows, ncols, nnz = case["nrows"], case["ncols"], case["nnz"]
    counts = r.multinomial(nnz, np.ones(nrows) / nrows) if nnz else \
        np.zeros(nrows, int)
    rows = np.zeros(nrows + 1, np.int64)
    rows[1:] = np.cumsum(counts)
    cols = r.integers(0, ncols, nnz)
    val = r.standard_normal(nnz).astype(np.float32)
    vec = r.standard_normal(ncols).astype(np.float32)
    vb, ri, ci, _, nrb = csr_to_bsr(rows, cols, val, ncols, bm=8, bk=128)
    out = dae_spmv(jnp.asarray(vb), jnp.asarray(ri), jnp.asarray(ci),
                   jnp.asarray(vec), nrb, rif=case["rif"],
                   interpret=True)[:nrows]
    ref = spmv_ref(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(val),
                   jnp.asarray(vec)) if nnz else np.zeros(nrows, np.float32)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def check_decode(case, seed=0):
    from repro.kernels.flash_attention import decode_ref, flash_decode
    from repro.kernels.flash_attention.ops import flash_decode_paged
    r = np.random.default_rng(seed)
    b, kvh, g, bk = case["b"], case["kvh"], case["g"], case["bk"]
    s = case["nblk"] * bk
    h = kvh * g
    q = jnp.asarray(r.standard_normal((b, h, 32)), jnp.float32)
    kc = jnp.asarray(r.standard_normal((b, kvh, s, 32)), jnp.float32)
    vc = jnp.asarray(r.standard_normal((b, kvh, s, 32)), jnp.float32)
    lens = jnp.asarray(r.integers(1, s + 1, b), jnp.int32)
    ref = decode_ref(q, kc, vc, lens)
    out = flash_decode(q, kc, vc, lens, bk=bk, rif=case["rif"],
                       interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    npb = s // bk
    kp = kc.transpose(0, 2, 1, 3).reshape(b * npb, bk, kvh, 32) \
        .transpose(0, 2, 1, 3)
    vp = vc.transpose(0, 2, 1, 3).reshape(b * npb, bk, kvh, 32) \
        .transpose(0, 2, 1, 3)
    pt = jnp.arange(b * npb, dtype=jnp.int32).reshape(b, npb)
    out2 = flash_decode_paged(q, kp, vp, pt, lens, rif=case["rif"],
                              interpret=True)
    np.testing.assert_allclose(out2, ref, rtol=2e-4, atol=2e-5)


def check_searchsorted(case, seed=0):
    from repro.kernels.dae_chase import batched_searchsorted, searchsorted_ref
    r = np.random.default_rng(seed)
    n, m = case["n"], case["m"]
    dtype = jnp.dtype(case["dtype"])
    if dtype == jnp.int32:
        # heavy duplicates: insertion points often straddle block edges
        table = jnp.sort(jnp.asarray(r.integers(0, max(2, n // 4), n), dtype))
        keys = jnp.asarray(r.integers(-2, max(2, n // 4) + 2, m), dtype)
    else:
        table = jnp.sort(jnp.asarray(r.standard_normal(n), dtype))
        keys = jnp.asarray(3 * r.standard_normal(m), dtype)
    out = batched_searchsorted(table, keys, block=case["block"],
                               chunk=case["chunk"], rif=case["rif"],
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(searchsorted_ref(table, keys)))


def check_hash(case, seed=0):
    from repro.kernels.dae_chase import hash_lookup, hash_lookup_ref
    r = np.random.default_rng(seed)
    chains, L, m = case["chains"], case["chain_len"], case["m"]
    n = chains * L
    ek = jnp.asarray(np.arange(n), jnp.int32)
    ev = jnp.asarray(r.integers(0, 1000, n), jnp.int32)
    en = jnp.asarray([(i + 1) if (i + 1) % L else -1 for i in range(n)],
                     jnp.int32)
    heads = jnp.asarray(r.integers(0, chains, m) * L, jnp.int32)
    depth = r.integers(0, L, m).astype(np.int32)
    present = heads + jnp.asarray(depth)
    missing = jnp.full(m, n + 17, jnp.int32)
    take_miss = r.random(m) < case["miss_rate"]
    keys = jnp.where(jnp.asarray(take_miss), missing, present)
    steps = max(1, L + case["extra_steps"])
    out = hash_lookup(ek, ev, en, heads, keys, max_steps=steps,
                      chunk=case["chunk"], rif=case["rif"], interpret=True)
    ref = hash_lookup_ref(ek, ev, en, heads, keys, steps)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def check_gmm(case, seed=0):
    from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_ref
    r = np.random.default_rng(seed)
    t, d, f, e, bt = case["t"], case["d"], case["f"], case["e"], case["bt"]
    nblk = -(-t // bt)
    # small-integer data: every partial product and partial sum is exactly
    # representable in float32, so pallas-vs-ref equality stays bitwise no
    # matter how bd splits the contraction into accumulated tiles
    x = jnp.asarray(r.integers(-4, 5, (t, d)), jnp.float32)
    w = jnp.asarray(r.integers(-4, 5, (e, d, f)), jnp.float32)
    hi = case.get("experts_used", e)
    blk = jnp.asarray(r.integers(0, hi, nblk), jnp.int32)
    out = grouped_matmul(x, w, blk, bt=bt, bf=case["bf"], bd=case["bd"],
                         rif=case["rif"], interpret=True)
    ref = grouped_matmul_ref(x, w, blk, bt)
    assert out.shape == (t, f)
    np.testing.assert_array_equal(_np(out), _np(ref))


# ---------------------------------------------------------------------------
# Deterministic edge-case grid (always runs)
# ---------------------------------------------------------------------------


GATHER_EDGES = [
    dict(n=40, d=128, m=17, chunk=8, rif=1, dtype="float32"),   # rif=1
    dict(n=40, d=128, m=17, chunk=8, rif=64, dtype="float32"),  # rif>chunk
    dict(n=7, d=130, m=5, chunk=64, rif=4, dtype="bfloat16"),   # tails
    dict(n=1, d=8, m=1, chunk=1, rif=1, dtype="float32"),       # singleton
    dict(n=13, d=128, m=9, chunk=4, rif=2, dtype="int32"),      # int rows
    dict(n=13, d=128, m=9, chunk=4, rif=2, dtype="int8"),       # narrow int
]

MERGE_EDGES = [
    dict(n=100, m=300, tile=64, rif=1, dtype="float32"),
    dict(n=100, m=300, tile=64, rif=64, dtype="float32"),       # rif>tiles
    dict(n=17, m=5, tile=16, rif=2, dtype="int32"),             # tails
    dict(n=0, m=3, tile=16, rif=3, dtype="float32"),            # empty run
]

SPMV_EDGES = [
    dict(nrows=16, ncols=256, nnz=64, rif=1),
    dict(nrows=16, ncols=256, nnz=64, rif=64),                  # rif>nb
    dict(nrows=33, ncols=300, nnz=120, rif=3),                  # tails
    dict(nrows=8, ncols=128, nnz=0, rif=2),                     # empty
]

DECODE_EDGES = [
    dict(b=2, kvh=2, g=4, nblk=4, bk=16, rif=1),
    dict(b=2, kvh=2, g=4, nblk=2, bk=16, rif=64),               # rif>nk
    dict(b=1, kvh=1, g=1, nblk=1, bk=64, rif=2),                # one block
]

SEARCHSORTED_EDGES = [
    dict(n=600, m=33, block=64, chunk=8, rif=1, dtype="float32"),
    dict(n=600, m=33, block=64, chunk=8, rif=64, dtype="float32"),
    dict(n=130, m=7, block=128, chunk=64, rif=4, dtype="int32"),  # tails
    dict(n=1, m=1, block=64, chunk=1, rif=1, dtype="int32"),
]

HASH_EDGES = [
    dict(chains=16, chain_len=4, m=37, chunk=8, rif=1, extra_steps=0,
         miss_rate=0.3),
    dict(chains=16, chain_len=4, m=37, chunk=8, rif=64, extra_steps=0,
         miss_rate=0.3),                                        # rif>chunk
    dict(chains=5, chain_len=3, m=11, chunk=64, rif=4, extra_steps=-2,
         miss_rate=0.0),                                        # short walk
    dict(chains=1, chain_len=1, m=1, chunk=1, rif=1, extra_steps=2,
         miss_rate=1.0),
]

GMM_EDGES = [
    dict(t=256, d=128, f=128, e=4, bt=128, bf=128, bd=128, rif=1),  # rif=1
    dict(t=256, d=128, f=128, e=4, bt=128, bf=128, bd=128,
         rif=64),                                          # rif > num blocks
    dict(t=300, d=200, f=130, e=3, bt=128, bf=128, bd=128,
         rif=2),                                           # tails everywhere
    dict(t=64, d=64, f=64, e=1, bt=64, bf=128, bd=128, rif=2),  # one expert
    dict(t=384, d=256, f=256, e=5, bt=128, bf=128, bd=128, rif=3,
         experts_used=2),                # empty expert groups, nd = nf = 2
    dict(t=0, d=16, f=8, e=2, bt=128, bf=128, bd=128, rif=2),   # T == 0
]


@pytest.mark.parametrize("method", ["rif", "pipelined"])
@pytest.mark.parametrize("case", GATHER_EDGES)
def test_gather_edges(case, method):
    check_gather(case, method=method)


@pytest.mark.parametrize("case", MERGE_EDGES)
def test_merge_edges(case):
    check_merge(case)


@pytest.mark.parametrize("case", SPMV_EDGES)
def test_spmv_edges(case):
    check_spmv(case)


@pytest.mark.parametrize("case", DECODE_EDGES)
def test_decode_edges(case):
    check_decode(case)


@pytest.mark.parametrize("case", SEARCHSORTED_EDGES)
def test_searchsorted_edges(case):
    check_searchsorted(case)


@pytest.mark.parametrize("case", HASH_EDGES)
def test_hash_edges(case):
    check_hash(case)


@pytest.mark.parametrize("case", GMM_EDGES)
def test_gmm_edges(case):
    check_gmm(case)


# ---------------------------------------------------------------------------
# Ring construction contracts
# ---------------------------------------------------------------------------


def test_chase_empty_inputs():
    """Zero probes/lookups short-circuit before the kernel (a (0,)-shaped
    operand cannot legally enter a pallas_call block)."""
    from repro.kernels.dae_chase import batched_searchsorted, hash_lookup
    table = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    out = batched_searchsorted(table, jnp.zeros((0,), jnp.float32),
                               interpret=True)
    assert out.shape == (0,) and out.dtype == jnp.int32
    ek = jnp.arange(4, dtype=jnp.int32)
    out = hash_lookup(ek, ek, jnp.full(4, -1, jnp.int32),
                      jnp.zeros((0,), jnp.int32),
                      jnp.zeros((0,), jnp.int32), interpret=True)
    assert out.shape == (0,) and out.dtype == jnp.int32


def test_gmm_rejects_bad_routing_length():
    """The routing stream must carry exactly one expert id per token
    block (including the tail block) — a mismatch is a caller bug the op
    refuses rather than silently truncating."""
    from repro.kernels.grouped_matmul import grouped_matmul
    x = jnp.zeros((200, 32), jnp.float32)
    w = jnp.zeros((2, 32, 16), jnp.float32)
    with pytest.raises(ValueError, match="2 token blocks"):
        grouped_matmul(x, w, jnp.zeros(3, jnp.int32), bt=128)


def test_ring_scratch_shapes_rejects_bad_depth():
    from repro.kernels.ring import ring_scratch_shapes
    with pytest.raises(ValueError, match="rif=0"):
        ring_scratch_shapes(0, (1, 8), jnp.float32)


def test_ring_channel_rejects_mismatched_scratch():
    import dataclasses as _dc
    from repro.kernels.ring import RingChannel

    fake = _dc.make_dataclass("FakeRef", [("shape", tuple)])((4, 1, 8))
    with pytest.raises(ValueError, match="rif=8"):
        RingChannel(fake, None, 8, src=lambda k: None)


# ---------------------------------------------------------------------------
# Chase dispatch order: explicit → tune cache → plan_rif
# ---------------------------------------------------------------------------


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    from repro.tune import reset_default_cache
    path = tmp_path / "tune_cache.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    reset_default_cache()
    yield path
    reset_default_cache()


def _capture_searchsorted_calls(monkeypatch):
    import repro.kernels.dae_chase.ops as chase_ops
    calls = []
    real = chase_ops._k.searchsorted_blocks

    def spy(tiles, blk, keys, n, *, chunk, rif, interpret):
        calls.append({"chunk": chunk, "rif": rif})
        return real(tiles, blk, keys, n, chunk=chunk, rif=rif,
                    interpret=interpret)

    monkeypatch.setattr(chase_ops._k, "searchsorted_blocks", spy)
    return calls


def test_chase_dispatch_order(tmp_cache, monkeypatch):
    from repro.core.pipeline import plan_rif
    from repro.kernels.dae_chase import batched_searchsorted, searchsorted_ref
    from repro.tune import CacheEntry, backend_tag, default_cache, make_key

    r = np.random.default_rng(0)
    table = jnp.sort(jnp.asarray(r.standard_normal(500), jnp.float32))
    keys = jnp.asarray(r.standard_normal(20), jnp.float32)
    calls = _capture_searchsorted_calls(monkeypatch)

    def run(**kw):
        out = batched_searchsorted(table, keys, interpret=True, **kw)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(searchsorted_ref(table, keys)))
        return calls[-1]

    # 3. empty cache: rif falls back to the plan_rif analytic seed (the
    # kernel itself clips the ring depth to the chunk afterwards)
    seen = run()
    assert seen["rif"] == plan_rif(128 * 4).rif

    # 2. a tuned winner in the cache beats the analytic seed
    key = make_key("batched_searchsorted", (500, 20), "float32",
                   backend_tag(True), "wallclock")
    default_cache().put(key, CacheEntry(
        config={"block": 64, "chunk": 16, "rif": 3}, score=1.0))
    seen = run()
    assert seen == {"chunk": 16, "rif": 3}

    # 1. explicit caller knobs beat the cache
    seen = run(chunk=4, rif=2)
    assert seen == {"chunk": 4, "rif": 2}


def test_hash_dispatch_plan_fallback(tmp_cache, monkeypatch):
    from repro.core.pipeline import plan_rif
    import repro.kernels.dae_chase.ops as chase_ops
    from repro.kernels.dae_chase import hash_lookup
    from repro.kernels.dae_chase.kernel import ENTRY_LANES

    calls = []
    real = chase_ops._k.hash_probe

    def spy(packed, heads, keys, *, chunk, rif, max_steps, interpret):
        calls.append({"chunk": chunk, "rif": rif})
        return real(packed, heads, keys, chunk=chunk, rif=rif,
                    max_steps=max_steps, interpret=interpret)

    monkeypatch.setattr(chase_ops._k, "hash_probe", spy)
    ek = jnp.arange(8, dtype=jnp.int32)
    out = hash_lookup(ek, ek * 10, jnp.full(8, -1, jnp.int32),
                      jnp.arange(4, dtype=jnp.int32),
                      jnp.arange(4, dtype=jnp.int32), max_steps=2,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.arange(4) * 10)
    assert calls[-1]["rif"] == plan_rif(ENTRY_LANES * 4).rif


def test_gmm_dispatch_order(tmp_cache, monkeypatch):
    from repro.core.pipeline import plan_rif
    import repro.kernels.grouped_matmul.ops as gmm_ops
    from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_ref
    from repro.tune import CacheEntry, backend_tag, default_cache, make_key

    calls = []
    real = gmm_ops._k.gmm

    def spy(x, w, blk, *, bt, bf, bd, rif, interpret):
        calls.append({"bf": bf, "bd": bd, "rif": rif})
        return real(x, w, blk, bt=bt, bf=bf, bd=bd, rif=rif,
                    interpret=interpret)

    monkeypatch.setattr(gmm_ops._k, "gmm", spy)

    r = np.random.default_rng(0)
    x = jnp.asarray(r.integers(-4, 5, (256, 192)), jnp.float32)
    w = jnp.asarray(r.integers(-4, 5, (3, 192, 128)), jnp.float32)
    blk = jnp.asarray([0, 2], jnp.int32)

    def run(**kw):
        gmm_ops._gmm_impl.clear_cache()    # retrace so the spy records
        out = grouped_matmul(x, w, blk, interpret=True, **kw)
        np.testing.assert_array_equal(
            _np(out), _np(grouped_matmul_ref(x, w, blk, 128)))
        return calls[-1]

    # 3. empty cache: bf/bd from the defaults (bd clipped to the padded
    # contraction), rif from the analytic plan over one weight tile
    seen = run()
    bd0 = 256                              # min(512, round_up(192, 128))
    assert seen == {"bf": 128, "bd": bd0,
                    "rif": plan_rif(bd0 * 128 * 4).rif}

    # 2. a tuned winner in the cache beats the analytic seed
    key = make_key("grouped_matmul", (256, 192, 128), "float32",
                   backend_tag(True), "wallclock")
    default_cache().put(key, CacheEntry(
        config={"bf": 64, "bd": 128, "rif": 3}, score=1.0))
    seen = run()
    assert seen == {"bf": 64, "bd": 128, "rif": 3}

    # 1. explicit caller knobs beat the cache
    seen = run(bf=128, bd=64, rif=2)
    assert seen == {"bf": 128, "bd": 64, "rif": 2}


# ---------------------------------------------------------------------------
# Hypothesis sweeps (CI tier; local runs skip without the extra)
# ---------------------------------------------------------------------------


# (only these sweeps skip without the extra — the deterministic grid
# above always runs, so the import cannot be a module-level importorskip)
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:
    pass
else:
    import strategies as repo_st  # tests/strategies.py

    SWEEP = settings(max_examples=12, deadline=None,
                     suppress_health_check=[HealthCheck.too_slow,
                                            HealthCheck.data_too_large])

    @SWEEP
    @given(case=repo_st.gather_cases(), seed=st.integers(0, 2**16))
    def test_gather_sweep_hypothesis(case, seed):
        check_gather(case, seed)

    @SWEEP
    @given(case=repo_st.merge_cases(), seed=st.integers(0, 2**16))
    def test_merge_sweep_hypothesis(case, seed):
        check_merge(case, seed)

    @SWEEP
    @given(case=repo_st.spmv_cases(), seed=st.integers(0, 2**16))
    def test_spmv_sweep_hypothesis(case, seed):
        check_spmv(case, seed)

    @SWEEP
    @given(case=repo_st.decode_cases(), seed=st.integers(0, 2**16))
    def test_decode_sweep_hypothesis(case, seed):
        check_decode(case, seed)

    @SWEEP
    @given(case=repo_st.searchsorted_cases(), seed=st.integers(0, 2**16))
    def test_searchsorted_sweep_hypothesis(case, seed):
        check_searchsorted(case, seed)

    @SWEEP
    @given(case=repo_st.hash_cases(), seed=st.integers(0, 2**16))
    def test_hash_sweep_hypothesis(case, seed):
        check_hash(case, seed)

    @SWEEP
    @given(case=repo_st.gmm_cases(), seed=st.integers(0, 2**16))
    def test_gmm_sweep_hypothesis(case, seed):
        check_gmm(case, seed)
