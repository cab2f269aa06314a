"""DeepSeek-V2-Lite on the serving path, at smoke widths on the CPU.

Against the benchmark's plain float32 reference
(``benchmarks/chip/reference_mla_moe.py``) on the benchmark's seeded
weights: prefill and paged decode through ``PagedServeLoop`` in both
kernel modes.  Then the pieces one by one: the absorbed latent
attention (one-KV-head MQA through the paged and contiguous caches)
against the un-absorbed form on the same weights; the held-expert layer's
shares summing to the uncut layer; YaRN against its closed form; the
router with and without renormalisation; and the explicit ``scale`` of
the two shared attention kernels.

Every comparison is of logits or activations, never of sampled tokens,
and each tolerance says why it is what it is.  The float32 program
differs from the float32 reference only by the order of its sums
(absorption, grouped and paged reductions): ~1e-6 of outputs of unit
scale.  Float8 operands move logits by ~1, so each tolerance below also
sits far under what the float8 control reads, which the first test
checks against the reference's own control.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models.common import attn_mscale, rope, yarn_inv_freq
from repro.models.moe import _route, moe_apply_held, moe_init
from repro.models.registry import build_model
from repro.runtime.serve_loop import PagedServeLoop, Request

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.append(str(CHIP))

import reference_mla_moe as REF  # noqa: E402
import weights as W  # noqa: E402

ARCH = "deepseek-v2-lite-16b"
SEED = 2**33 + 17
# 16 experts of which this device holds 8, the published top-6
SMOKE = dict(n_experts=16, top_k=6, expert_range=(0, 8))
# logits of the float32 program against the float32 reference: only the
# order of sums differs (5e-6 read on logits of magnitude up to ~4);
# 1e-4 leaves that room twenty times and is ~10^4 x under float8's (~1)
LOGIT_TOL = 1e-4


def _cfg(**over):
    return get_config(ARCH, smoke=True, **dict(SMOKE, **over))


def _model_block(cfg) -> dict:
    """The configuration's ``model`` block at the smoke widths of ``cfg``."""
    cf = json.loads((CHIP / "configs" / "deepseek-v2-lite.json").read_text())
    lo, hi = cfg.held_experts
    assert lo == 0
    return dict(cf["model"], n_layers=cfg.n_layers,
                first_dense_layers=cfg.first_dense_layers,
                d_model=cfg.d_model, n_heads=cfg.n_heads,
                kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope,
                qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_hd,
                d_ff=cfg.d_ff, moe_d_ff=cfg.moe_d_ff,
                n_experts=cfg.n_experts, n_experts_held=hi - lo,
                top_k=cfg.top_k, n_shared_experts=cfg.n_shared_experts,
                vocab=cfg.vocab, eps=cfg.norm_eps)


# -- (a) the serving path against the reference --------------------------------


@pytest.mark.parametrize("mode", ["pallas", "ref"])
def test_paged_serving_matches_reference(mode):
    """Prefill in chunks of 6 on pages of 8 (chunks end mid-page), ragged
    prompts of 3-19 tokens, decode across page boundaries, four requests
    on three slots (one slot reused): every logits row a token was
    chosen from agrees with the reference's full forward."""
    cfg = _cfg(kernel_mode=mode)
    m = _model_block(cfg)
    bundle = build_model(cfg)
    params = W.make_params(m, SEED, "float32")
    W.check_layout(params, jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    prompts = {rid: rng.integers(0, cfg.vocab, n)
               for rid, n in enumerate((19, 5, 11, 3))}
    loop = PagedServeLoop(cfg, bundle, params, batch_slots=3, s_max=32,
                          chunk=6, page=8, record_logits=True)
    out = loop.run([Request(rid=r, prompt=p, max_new=7 + r)
                    for r, p in prompts.items()])
    seqs = [np.concatenate([prompts[r], out[r][:-1]]) for r in prompts]
    rows = [np.arange(len(prompts[r]) - 1, len(prompts[r]) - 1 + len(out[r]))
            for r in prompts]
    ref = REF.logits_at(m, "float32", SEED, seqs, rows, bucket=32)
    got = [np.stack(loop.token_logits[r]) for r in prompts]
    err = max(float(np.abs(g - f).max()) for g, f in zip(got, ref))
    assert err < LOGIT_TOL, err
    assert loop.stats.moe_rows > 0
    assert loop.stats.moe_blocks * 128 >= loop.stats.moe_rows
    # the tolerance is one float8 operands fail
    low = REF.logits_at(m, "float32", SEED, seqs, rows, bucket=32,
                        control=True)
    ctl = max(float(np.abs(c - f).max()) for c, f in zip(low, ref))
    assert ctl > 30 * LOGIT_TOL, ctl


# -- (b) absorbed against un-absorbed -------------------------------------------


def _mla(mode):
    cfg = _cfg(kernel_mode=mode)
    p = attn.mla_init(cfg, jax.random.PRNGKey(1))
    # a norm gain off 1, so a missing or misplaced gain shows
    p = dict(p, kv_norm=1 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["kv_norm"].shape))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 13, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(13, dtype=jnp.int32), (2, 13))
    full, _ = attn.mla_apply(cfg, p, x, pos)          # un-absorbed, causal
    return cfg, p, x, full


# absorbed and un-absorbed MLA in float32 differ by the order of the
# latent sums (q W_uk^T c against q (c W_uk)): ~1e-6 of outputs of unit
# scale; 1e-4 is tight enough that a wrong scale, rope or lane fails
MLA_TOL = 1e-4


@pytest.mark.parametrize("mode", ["pallas", "ref"])
def test_absorbed_paged_matches_unabsorbed(mode):
    """A chunk of 5, four single-token decode steps, then a chunk of 4 in
    which the second row holds only 2 valid tokens, through the paged
    latent pool (pages of 4, scattered page table): each valid
    position's output equals the un-absorbed causal forward's."""
    cfg, p, x, full = _mla(mode)
    b, page, npb = 2, 4, 4
    lanes = attn.mla_latent_lanes(cfg)
    table = jnp.array([[3, 7, 1, 5], [2, 8, 6, 4]], jnp.int32)
    cache = {"latp": jnp.zeros((1 + b * npb, 1, page, lanes)),
             "len": jnp.zeros((b,), jnp.int32)}
    outs = []

    def step(lo, hi, valid=None):
        nonlocal cache
        pos = cache["len"][:, None] + jnp.arange(hi - lo, dtype=jnp.int32)
        y, cache = attn.mla_apply(cfg, p, x[:, lo:hi], pos, cache=cache,
                                  valid=valid, page_table=table)
        outs.append((lo, y))

    step(0, 5)
    for t in range(5, 9):
        step(t, t + 1, jnp.ones((b, 1), bool))
    step(9, 13, jnp.array([[True] * 4, [True, True, False, False]]))
    assert list(np.asarray(cache["len"])) == [13, 11]
    for lo, y in outs:
        n = y.shape[1]
        np.testing.assert_allclose(np.asarray(y[0]),
                                   np.asarray(full[0, lo:lo + n]),
                                   rtol=MLA_TOL, atol=MLA_TOL)
        keep = min(n, 11 - lo)
        np.testing.assert_allclose(np.asarray(y[1, :keep]),
                                   np.asarray(full[1, lo:lo + keep]),
                                   rtol=MLA_TOL, atol=MLA_TOL)


@pytest.mark.parametrize("mode", ["pallas", "ref"])
def test_absorbed_contiguous_matches_unabsorbed(mode):
    """The contiguous latent cache: a masked chunk of 6, then
    single-token decode steps with no mask (``flash_decode`` in pallas
    mode)."""
    cfg, p, x, full = _mla(mode)
    b = 2
    cache = {"lat": jnp.zeros((b, 1, 16, attn.mla_latent_lanes(cfg))),
             "len": jnp.zeros((b,), jnp.int32)}
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (b, 6))
    y, cache = attn.mla_apply(cfg, p, x[:, :6], pos, cache=cache,
                              valid=jnp.ones((b, 6), bool))
    np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, :6]),
                               rtol=MLA_TOL, atol=MLA_TOL)
    for t in range(6, 9):
        y, cache = attn.mla_apply(cfg, p, x[:, t:t + 1],
                                  jnp.full((b, 1), t, jnp.int32), cache=cache)
        np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(full[:, t]),
                                   rtol=MLA_TOL, atol=MLA_TOL)


@pytest.mark.parametrize("chunk", [1, 4])
def test_pallas_paged_steps_use_the_kernels(monkeypatch, chunk):
    """In pallas mode a paged step never reaches the per-query oracle nor
    the un-absorbed up-projection of the cache: a decode step calls
    ``flash_decode_paged``, a chunk ``grouped_chunk_attention``."""
    calls = []

    def banned(*a, **k):
        raise AssertionError("the ref-mode oracle ran in pallas mode")

    monkeypatch.setattr(attn, "decode_chunk_ref", banned)
    monkeypatch.setattr(attn, "_prefill_attention", banned)
    for name in ("flash_decode_paged", "grouped_chunk_attention"):
        fn = getattr(attn, name)
        monkeypatch.setattr(attn, name, lambda *a, _f=fn, _n=name, **k: (
            calls.append(_n), _f(*a, **k))[1])
    cfg = _cfg(kernel_mode="pallas")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, page, npb = 2, 8, 2
    cache = m.cache_init_paged(b, 1 + b * npb, page)
    table = 1 + jnp.arange(b * npb, dtype=jnp.int32).reshape(b, npb)
    logits, _ = m.prefill_paged(params, cache,
                                jnp.ones((b, chunk), jnp.int32),
                                jnp.array([3, 0], jnp.int32),
                                jnp.array([chunk, 1], jnp.int32), table)
    assert np.isfinite(np.asarray(logits)).all()
    want = "flash_decode_paged" if chunk == 1 else "grouped_chunk_attention"
    assert calls and set(calls) == {want}, calls


def test_latent_pool_is_one_kv_head_of_640_lanes():
    """At published widths the pool is (NP, 1, PAGE, 640): 512 + 64
    values padded to five 128-lane tiles."""
    cfg = get_config(ARCH, expert_range=(0, 8))
    assert attn.mla_latent_lanes(cfg) == 640
    shape = jax.eval_shape(
        lambda: build_model(cfg).cache_init_paged(4, 9, 16))
    assert shape[0]["attn"]["latp"].shape == (1, 9, 1, 16, 640)
    assert shape[1]["attn"]["latp"].shape == (26, 9, 1, 16, 640)
    assert set(shape[1]["stats"]) == {"moe_rows", "moe_blocks"}


# -- (c) the held shares of an expert layer -------------------------------------


@pytest.mark.parametrize("mode", ["pallas", "ref"])
def test_held_shares_sum_to_the_uncut_layer(mode):
    """64 experts in 8 shares of 8 (the deployment's cut): each share
    routes over all 64 and computes its own experts' part; with the
    shared experts, which every share computes alike, counted once, the
    shares add up to the uncut layer, and to the layer written out.
    Float32 sums in another order: ~1e-6 of outputs of unit scale."""
    full_cfg = get_config(ARCH, smoke=True, n_experts=64, top_k=6,
                          kernel_mode=mode)
    p = moe_init(full_cfg, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, full_cfg.d_model))
    whole, st = moe_apply_held(full_cfg, p, x)
    total, rows = 0.0, 0
    for lo in range(0, 64, 8):
        cfg = get_config(ARCH, smoke=True, n_experts=64, top_k=6,
                         kernel_mode=mode, expert_range=(lo, lo + 8))
        share = dict(p, **{k: p[k][lo:lo + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        y, s = moe_apply_held(cfg, share, x)
        total = total + y
        rows += int(s["moe_rows"])
    from repro.models.mlp import mlp_apply
    x2d = x.reshape(-1, x.shape[-1])
    shared = mlp_apply(full_cfg, p["shared"], x2d).reshape(x.shape)
    total = total - 7 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    # every routed row lands in exactly one share
    assert rows == int(st["moe_rows"]) == 16 * 6

    # the layer written out: softmax over 64, greedy top-6, no
    # renormalisation, plus the shared experts
    probs = jax.nn.softmax(x2d @ p["router"], -1)
    gates, idx = jax.lax.top_k(probs, 6)
    y = shared.reshape(x2d.shape)
    for k in range(6):
        e = idx[:, k]
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", x2d, p["w_gate"][e]))
        h = h * jnp.einsum("td,tdf->tf", x2d, p["w_up"][e])
        y = y + gates[:, k:k + 1] * jnp.einsum("tf,tfd->td", h,
                                               p["w_down"][e])
    np.testing.assert_allclose(np.asarray(whole.reshape(x2d.shape)),
                               np.asarray(y), rtol=1e-4, atol=1e-4)


def test_held_layer_pallas_matches_the_dense_oracle_below_top_k():
    """Fewer experts held (4) than a token meets (6): most rows are held
    elsewhere and contribute nothing; the grouped path equals the plain
    oracle (float32 sums in another order: 1e-5)."""
    cfg_p = _cfg(kernel_mode="pallas", expert_range=(4, 8))
    cfg_r = _cfg(kernel_mode="ref", expert_range=(4, 8))
    p = moe_init(cfg_p, jax.random.PRNGKey(12))
    x = jax.random.normal(jax.random.PRNGKey(13), (3, 8, cfg_p.d_model))
    y_p, s_p = moe_apply_held(cfg_p, p, x)
    y_r, s_r = moe_apply_held(cfg_r, p, x)
    assert np.isfinite(np.asarray(y_p)).all()
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    assert int(s_p["moe_rows"]) == int(s_r["moe_rows"]) > 0


@pytest.mark.parametrize("n_experts,tokens,min_blocks", [
    (8, (4, 128), 17),    # every row held: the live blocks fill two segments
    (16, (2, 128), 1),    # 8 of 16 held, as the cell: one segment of several
])
def test_held_layer_pallas_loop_over_segments_matches_the_dense_oracle(
        n_experts, tokens, min_blocks):
    """A step of many rows (a prefill of 4,096 in the served cell) sizes
    the grouped buffer for more blocks than one call runs (``2 * e``), so
    the products run in a loop over the live segments only.  With a
    chunk's padding rows routed nowhere, the grouped path equals the
    plain oracle (float32 sums in another order: 1e-5)."""
    cfg_p = _cfg(kernel_mode="pallas", n_experts=n_experts,
                 expert_range=(0, 8))
    cfg_r = _cfg(kernel_mode="ref", n_experts=n_experts, expert_range=(0, 8))
    p = moe_init(cfg_p, jax.random.PRNGKey(14))
    x = jax.random.normal(jax.random.PRNGKey(15), tokens + (cfg_p.d_model,))
    valid = jnp.arange(tokens[1])[None, :] < jnp.array(
        [tokens[1] - 11 * i for i in range(tokens[0])])[:, None]
    t, k, e = tokens[0] * tokens[1], cfg_p.top_k, 8
    # the loop branch: more buffer blocks than one call's 2 * e
    assert (-(-t * min(k, e) // 128) + e) > 2 * e
    y_p, s_p = moe_apply_held(cfg_p, p, x, valid)
    y_r, s_r = moe_apply_held(cfg_r, p, x, valid)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    assert int(s_p["moe_rows"]) == int(s_r["moe_rows"]) > 0
    assert int(s_p["moe_blocks"]) >= min_blocks


def test_held_layer_skips_invalid_rows():
    """A chunk's padding rows route nowhere: they add no rows and leave
    the valid rows' outputs as they are."""
    cfg = _cfg(kernel_mode="pallas")
    p = moe_init(cfg, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 4, cfg.d_model))
    valid = jnp.array([[True, True, False, False], [True] * 4])
    y_all, s_all = moe_apply_held(cfg, p, x)
    y, s = moe_apply_held(cfg, p, x, valid)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)],
                               np.asarray(y_all)[np.asarray(valid)],
                               rtol=1e-5, atol=1e-5)
    assert int(s["moe_rows"]) < int(s_all["moe_rows"])


# -- (d) YaRN --------------------------------------------------------------------


def test_yarn_frequencies_and_mscale_follow_the_closed_form():
    """DeepSeek-V2-Lite's rope_scaling (factor 40 over 4,096 positions,
    beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707) on its 64
    rope dims, from the published formula: the correction range is
    [floor(d ln(L / (2 pi 32)) / (2 ln b)), ceil(d ln(L / (2 pi)) / (2 ln
    b))] = [10, 23]; below it the original frequencies, above it those
    over 40, a linear ramp between.  The softmax scale gains (0.1 * 0.707
    * ln 40 + 1)^2 = 1.2608^2, the cos/sin factor is 1."""
    cfg = get_config(ARCH)
    want = []
    for i in range(32):
        base = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        want.append(base * (1 - ramp) + base / 40 * ramp)
    np.testing.assert_allclose(yarn_inv_freq(cfg, 64, 10000.0), want,
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert attn_mscale(cfg) == pytest.approx(m * m, rel=1e-12)
    # the rotation with those frequencies, on the two halves
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 3, 64))
    pos = jnp.array([[0, 7, 3000]])
    ang = np.asarray(pos)[0][:, None] * np.asarray(want)[None, :]
    c, s = np.cos(ang), np.sin(ang)
    x1, x2 = np.asarray(x)[0, :, :32], np.asarray(x)[0, :, 32:]
    ref = np.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    np.testing.assert_allclose(np.asarray(rope(x, pos, 10000.0, cfg))[0],
                               ref, rtol=1e-4, atol=1e-4)
    # the reference's transcription agrees
    ys = {"factor": 40, "original_max_position_embeddings": 4096,
          "beta_fast": 32, "beta_slow": 1}
    np.testing.assert_allclose(REF.yarn_inv_freq(64, 10000.0, ys), want,
                               rtol=1e-6)


def test_rope_without_yarn_is_unchanged():
    """A config without YaRN rotates exactly as before, bit for bit."""
    cfg = get_config("qwen3-4b")
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 128))
    pos = jnp.arange(5)[None].repeat(2, 0) * 37
    half = 64
    freqs = jnp.exp(-math.log(cfg.rope_theta)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    old = jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                           x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)
    for new in (rope(x, pos, cfg.rope_theta),
                rope(x, pos, cfg.rope_theta, cfg)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# -- (e) routing ------------------------------------------------------------------


@pytest.mark.parametrize("norm", [False, True])
def test_route_renormalises_only_when_asked(norm):
    """``norm_topk_prob`` false (DeepSeek-V2-Lite) keeps the top-6 softmax
    probabilities as they are; true divides them by their sum."""
    cfg = get_config(ARCH, smoke=True, n_experts=64, top_k=6,
                     norm_topk_prob=norm)
    p = moe_init(cfg, jax.random.PRNGKey(10))
    x2d = jax.random.normal(jax.random.PRNGKey(11), (5, cfg.d_model))
    gates, experts = _route(cfg, p, x2d)
    probs = np.asarray(jax.nn.softmax(x2d @ p["router"], -1))
    top = np.take_along_axis(probs, np.asarray(experts), 1)
    np.testing.assert_allclose(np.sort(top, 1)[:, ::-1],
                               np.sort(probs, 1)[:, ::-1][:, :6], rtol=1e-6)
    want = top / top.sum(1, keepdims=True) if norm else top
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    assert (np.asarray(gates).sum(1) < 0.999).all() != norm


def test_published_config_fields():
    cfg = get_config(ARCH)
    assert not cfg.norm_topk_prob and cfg.moe_d_ff == 1408
    assert cfg.yarn_factor == 40 and cfg.yarn_original_max_pos == 4096
    assert get_config("granite-moe-3b-a800m").norm_topk_prob


# -- the kernels' explicit scale --------------------------------------------------


def test_kernels_honour_an_explicit_scale():
    """``flash_decode_paged`` and ``grouped_chunk_attention`` at an
    explicit ``scale`` equal their oracles at that scale (float32 sums in
    another order: 1e-5); left out, the scale is ``D ** -0.5`` bit for
    bit, as the existing callers had it."""
    from repro.kernels.flash_attention.ops import flash_decode_paged
    from repro.kernels.flash_attention.ref import decode_chunk_ref, decode_ref
    rng = np.random.default_rng(12)
    b, h, d, page, npb = 3, 4, 128, 8, 3
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    pages = jnp.asarray(rng.standard_normal((1 + b * npb, 1, page, d)),
                        jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * npb).reshape(b, npb),
                        jnp.int32)
    lens = jnp.array([5, 17, 24], jnp.int32)
    cont = jnp.take(pages, table, 0).transpose(0, 2, 1, 3, 4).reshape(
        b, 1, npb * page, d)
    for scale in (0.3, None):
        got = flash_decode_paged(q, pages, pages, table, lens, scale=scale)
        want = decode_ref(q, cont, cont, lens, scale=scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(flash_decode_paged(q, pages, pages, table, lens)),
        np.asarray(flash_decode_paged(q, pages, pages, table, lens,
                                      scale=d ** -0.5)))

    c = 5
    qc = jnp.asarray(rng.standard_normal((b, h, c, d)), jnp.float32)
    qlens = lens[:, None] - c + 1 + jnp.arange(c)[None]
    for scale in (0.3, None):
        got = attn.grouped_chunk_attention(qc, cont, cont, qlens, scale=scale)
        want = decode_chunk_ref(qc, cont, cont, qlens, scale=scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(attn.grouped_chunk_attention(qc, cont, cont, qlens)),
        np.asarray(attn.grouped_chunk_attention(qc, cont, cont, qlens,
                                                scale=d ** -0.5)))
