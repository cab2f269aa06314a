"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

R = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-5)


# -- dae_gather ---------------------------------------------------------------


@pytest.mark.parametrize("n,d,m", [(64, 128, 16), (100, 256, 33),
                                   (37, 130, 7), (512, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("method", ["pipelined", "rif"])
def test_gather_sweep(n, d, m, dtype, method):
    from repro.kernels.dae_gather import dae_gather, gather_ref
    table = jnp.asarray(R.standard_normal((n, d)), dtype)
    idx = jnp.asarray(R.integers(0, n, m), jnp.int32)
    out = dae_gather(table, idx, method=method, chunk=8, rif=4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gather_ref(table, idx), np.float32))


# -- dae_spmv -----------------------------------------------------------------


@pytest.mark.parametrize("n,m,nnz", [(16, 256, 64), (33, 300, 120),
                                     (8, 128, 0)])
def test_spmv_sweep(n, m, nnz):
    from repro.kernels.dae_spmv import (bsr_spmv_ref, csr_to_bsr, dae_spmv,
                                        spmv_ref)
    counts = R.multinomial(nnz, np.ones(n) / n) if nnz else np.zeros(n, int)
    rows = np.zeros(n + 1, np.int64)
    rows[1:] = np.cumsum(counts)
    cols = R.integers(0, m, nnz)
    val = R.standard_normal(nnz).astype(np.float32)
    vec = R.standard_normal(m).astype(np.float32)
    vb, ri, ci, _, nrb = csr_to_bsr(rows, cols, val, m)
    out = dae_spmv(jnp.asarray(vb), jnp.asarray(ri), jnp.asarray(ci),
                   jnp.asarray(vec), nrb)[:n]
    ref = spmv_ref(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(val),
                   jnp.asarray(vec)) if nnz else np.zeros(n, np.float32)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


# -- dae_merge ----------------------------------------------------------------


@pytest.mark.parametrize("n,m", [(256, 256), (100, 300), (17, 5), (64, 0),
                                 (1, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_merge_sweep(n, m, dtype):
    from repro.kernels.dae_merge import merge_ref, merge_sorted
    if dtype == jnp.int32:
        a = jnp.sort(jnp.asarray(R.integers(0, 50, n), dtype))
        b = jnp.sort(jnp.asarray(R.integers(0, 50, max(m, 1))[:m], dtype))
    else:
        a = jnp.sort(jnp.asarray(R.standard_normal(n), dtype))
        b = jnp.sort(jnp.asarray(R.standard_normal(max(m, 1))[:m], dtype))
    out = merge_sorted(a, b, tile=64)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(merge_ref(a, b)))


def test_merge_sort_full():
    from repro.kernels.dae_merge import merge_sort
    x = jnp.asarray(R.integers(0, 10_000, 777), jnp.int32)
    np.testing.assert_array_equal(np.asarray(merge_sort(x, tile=64)),
                                  np.sort(np.asarray(x)))


# -- dae_chase ----------------------------------------------------------------


@pytest.mark.parametrize("n,b", [(1000, 37), (130, 8), (5000, 256)])
def test_searchsorted_sweep(n, b):
    from repro.kernels.dae_chase import batched_searchsorted, searchsorted_ref
    table = jnp.sort(jnp.asarray(R.standard_normal(n), jnp.float32))
    keys = jnp.asarray(R.standard_normal(b), jnp.float32)
    out = batched_searchsorted(table, keys, block=128)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(searchsorted_ref(table, keys)))


def test_hash_lookup_chains():
    from repro.kernels.dae_chase import hash_lookup, hash_lookup_ref
    n, chains, L = 64, 16, 4
    ek = jnp.asarray(np.arange(n), jnp.int32)
    ev = jnp.asarray(R.integers(0, 1000, n), jnp.int32)
    en = jnp.asarray([(i + 1) if (i + 1) % L else -1 for i in range(n)],
                     jnp.int32)
    heads = jnp.asarray([L * c for c in range(chains)], jnp.int32)
    keys = jnp.asarray([L * c + L - 1 for c in range(chains)], jnp.int32)
    out = hash_lookup(ek, ev, en, heads, keys, max_steps=L)
    ref = hash_lookup_ref(ek, ev, en, heads, keys, L)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # missing key -> -1
    missing = hash_lookup(ek, ev, en, heads, heads * 0 + 10_000, max_steps=L)
    assert (np.asarray(missing) == -1).all()


# -- flash attention ----------------------------------------------------------


@pytest.mark.parametrize("b,h,kvh,s,d,causal,window", [
    (2, 4, 2, 256, 64, True, None),
    (1, 8, 1, 100, 32, True, None),
    (2, 4, 4, 128, 64, False, None),
    (1, 4, 2, 256, 64, True, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(b, h, kvh, s, d, causal, window, dtype):
    from repro.kernels.flash_attention import attention_ref, flash_attention
    q = jnp.asarray(R.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(R.standard_normal((b, kvh, s, d)), dtype)
    v = jnp.asarray(R.standard_normal((b, kvh, s, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_decode_and_paged():
    from repro.kernels.flash_attention import decode_ref, flash_decode
    from repro.kernels.flash_attention.ops import flash_decode_paged
    b, h, kvh, s, d = 2, 8, 2, 256, 64
    q = jnp.asarray(R.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(R.standard_normal((b, kvh, s, d)), jnp.float32)
    vc = jnp.asarray(R.standard_normal((b, kvh, s, d)), jnp.float32)
    lens = jnp.asarray([100, 256], jnp.int32)
    out = flash_decode(q, kc, vc, lens, bk=64)
    ref = decode_ref(q, kc, vc, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    page = 64
    npb = s // page
    kp = kc.transpose(0, 2, 1, 3).reshape(b * npb, page, kvh, d).transpose(0, 2, 1, 3)
    vp = vc.transpose(0, 2, 1, 3).reshape(b * npb, page, kvh, d).transpose(0, 2, 1, 3)
    pt = jnp.arange(b * npb, dtype=jnp.int32).reshape(b, npb)
    out2 = flash_decode_paged(q, kp, vp, pt, lens)
    np.testing.assert_allclose(out2, ref, rtol=2e-4, atol=2e-5)


def test_chunked_attention_matches_ref():
    from repro.kernels.flash_attention.ref import (attention_chunked,
                                                   attention_ref)
    q = jnp.asarray(R.standard_normal((2, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(R.standard_normal((2, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(R.standard_normal((2, 2, 256, 64)), jnp.float32)
    for caus, win in [(True, None), (True, 64), (False, None)]:
        out = attention_chunked(q, k, v, causal=caus, window=win, chunk=64)
        ref = attention_ref(q, k, v, causal=caus, window=win)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


# -- grouped matmul -----------------------------------------------------------


@pytest.mark.parametrize("t,d,f,e,bt", [(256, 192, 160, 4, 64),
                                        (128, 128, 128, 2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(t, d, f, e, bt, dtype):
    from repro.kernels.grouped_matmul import grouped_matmul, grouped_matmul_ref
    x = jnp.asarray(R.standard_normal((t, d)), dtype)
    w = jnp.asarray(R.standard_normal((e, d, f)), dtype)
    be = jnp.asarray(np.sort(R.integers(0, e, t // bt)), jnp.int32)
    out = grouped_matmul(x, w, be, bt=bt)
    ref = grouped_matmul_ref(x, w, be, bt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_banded_attention_matches_ref():
    from repro.kernels.flash_attention.ref import (attention_banded,
                                                   attention_ref)
    q = jnp.asarray(R.standard_normal((1, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(R.standard_normal((1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(R.standard_normal((1, 2, 256, 32)), jnp.float32)
    for w, c in [(64, 32), (64, 64), (200, 64)]:
        ref = attention_ref(q, k, v, causal=True, window=w)
        for unroll in (False, True):
            out = attention_banded(q, k, v, window=w, chunk=c, unroll=unroll)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


# -- dispatch helpers ---------------------------------------------------------


def test_decode_chunk_ref_matches_decode_ref():
    """The chunked-prefill oracle must be bit-identical to per-query
    decode_ref calls (serving parity depends on it)."""
    from repro.kernels.flash_attention.ref import decode_chunk_ref, decode_ref
    b, h, kvh, c, s, d = 2, 4, 2, 3, 32, 16
    q = jnp.asarray(R.standard_normal((b, h, c, d)), jnp.float32)
    kc = jnp.asarray(R.standard_normal((b, kvh, s, d)), jnp.float32)
    vc = jnp.asarray(R.standard_normal((b, kvh, s, d)), jnp.float32)
    lens = jnp.asarray(R.integers(1, s, (b, c)), jnp.int32)
    out = decode_chunk_ref(q, kc, vc, lens)
    for i in range(c):
        np.testing.assert_array_equal(
            np.asarray(out[:, :, i]),
            np.asarray(decode_ref(q[:, :, i], kc, vc, lens[:, i])))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("c", [1, 5, 32])
@pytest.mark.parametrize("g", [1, 4, 8])
def test_grouped_chunk_attention_matches_oracle(g, c, dtype, monkeypatch):
    """The pallas-mode chunk path (one grouped GEMM per KV head, rows in
    blocks) against the per-query oracle: ragged per-row lengths, one
    row at its first position and one ending at the horizon, and a
    batch of 5 in blocks of 2 (a remainder block of 1)."""
    from repro.kernels.flash_attention.ref import decode_chunk_ref
    from repro.models import attention
    b, kvh, s, d = 5, 2, 48, 16
    h = g * kvh
    monkeypatch.setattr(attention, "_CHUNK_SCORE_BYTES",
                        2 * (2 * 4 * h * c * s) + 1)      # 2 rows a block
    q = jnp.asarray(R.standard_normal((b, h, c, d)), dtype)
    kc = jnp.asarray(R.standard_normal((b, kvh, s, d)), dtype)
    vc = jnp.asarray(R.standard_normal((b, kvh, s, d)), dtype)
    pos = np.array([0, s - c, *R.integers(0, s - c + 1, b - 2)])
    lens = jnp.asarray(pos[:, None] + np.arange(1, c + 1)[None], jnp.int32)
    out = jax.jit(attention.grouped_chunk_attention)(q, kc, vc, lens)
    ref = jax.jit(decode_chunk_ref)(q, kc, vc, lens)
    assert out.shape == ref.shape and out.dtype == dtype
    # float32 rounding; a bfloat16 output may round one step apart
    tol = (dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32
           else dict(rtol=2.0 ** -7, atol=1e-6))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


@pytest.mark.parametrize("raw,expect", [
    (None, False),        # unset: fall through to the backend check
    ("0", False), ("false", False), ("", False), ("no", False),
    ("off", False), ("  FALSE  ", False),
    ("1", True), ("true", True), ("yes", True), ("interpret", True),
])
def test_resolve_interpret_env_parsing(raw, expect, monkeypatch):
    """Regression: REPRO_FORCE_INTERPRET=0/false/empty used to force
    interpret ON (any non-empty string was truthy)."""
    from repro.kernels.common import resolve_interpret
    if raw is None:
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", raw)
    # pretend we are on TPU so the backend fallback returns False and
    # the env var's parse is the only thing that can flip the result
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is expect
    # an explicit caller value still always wins
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
