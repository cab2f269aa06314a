#!/usr/bin/env python3
"""Self-check of the benchmark's yardstick, on the CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/selfcheck.py

Checks, and prints one line each:

1. the plain reference (``reference.py``) against the program's own
   full-sequence forward (``repro.models``) at smoke widths, in float32,
   for both configurations' kinds (qwen3-4b's head tied), with the
   weights of ``weights.py``;
   and that the served weights equal the reference's, rounded;
2. the trace reduction (``reduce_trace.py``) on a hand-made trace
   whose answers are known, and on the small recorded chip trace in
   ``testdata/`` against a brute-force count on a 1 us grid;
3. the percentile, gap and rate arithmetic (``stats.py``) against
   numpy and hand-worked cases.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def check_reference() -> None:
    import jax
    import jax.numpy as jnp
    import reference as R
    import weights as W
    from repro.configs import get_config
    from repro.models.registry import build_model

    for arch, mlp, tied in (("qwen3-4b", "swiglu", True),
                            ("granite-34b", "gelu", False)):
        cfg = get_config(arch, smoke=True, tie_embeddings=tied)
        m = dict(family="dense", tie_embeddings=tied,
                 n_layers=cfg.n_layers, d_model=cfg.d_model,
                 n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                 head_dim=cfg.hd, d_ff=cfg.d_ff, vocab=cfg.vocab,
                 qk_norm=cfg.qk_norm, mlp=mlp, rope_theta=cfg.rope_theta,
                 eps=cfg.norm_eps)
        bundle = build_model(cfg)
        seed = 2**33 + 7
        params = W.make_params(m, seed, "float32")
        W.check_layout(params, jax.eval_shape(bundle.init,
                                              jax.random.PRNGKey(0)))
        bf = W.make_params(m, seed, "bfloat16")
        same = all(
            np.array_equal(
                np.asarray(bf["segments"][0][k1][k2][i], np.float32),
                np.asarray(W.reference_layer(m, seed, i, "bfloat16")[k1][k2]))
            for i in range(m["n_layers"])
            for k1, k2 in (("attn", "wq"), ("mlp", "w_up")))
        toks = np.random.default_rng(0).integers(0, cfg.vocab, (2, 40))
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(bundle.apply(params, jnp.asarray(toks)))
        rows = [np.arange(40), np.arange(5, 30)]
        ref = R.logits_at(m, "float32", seed, [toks[0], toks[1][:30]], rows,
                          bucket=16, qblock=16)
        d = max(np.abs(ref[0] - lg[0]).max(),
                np.abs(ref[1] - lg[1][5:30]).max())
        ok = d < 1e-4 and same
        print(f"reference vs repro.models ({arch} smoke"
              f"{', tied head' if tied else ''}, float32): max "
              f"|logit diff| {d:.3g}; served weights = rounded reference "
              f"weights: {same} -> {'ok' if ok else 'FAIL'}")
        assert ok


def brute_busy(ops, t0, t1, step=1e-6):
    grid = np.zeros(int(round((t1 - t0) / step)), bool)
    for a, b, _ in ops:
        i, j = int(round((max(a, t0) - t0) / step)), int(round((min(b, t1) - t0) / step))
        if j > i:
            grid[i:j] = True
    return grid.sum() * step


def check_trace() -> None:
    import reduce_trace as T

    ops = [(0.0, 1.0, "while.1"), (0.1, 0.4, "fusion.2"),
           (0.5, 0.9, "_decode_paged_impl.3"), (1.5, 2.0, "fusion.2")]
    spans = [(0.0, 1.2, "serve.decode_step"), (1.4, 2.1, "serve.prefill_step")]
    assert abs(T.busy_s(ops, 0.0, 2.5) - 1.5) < 1e-12
    assert abs(T.kernel_s(ops, r"_decode_paged_impl(\.\d+)?$", 0, 2.5)
               - 0.4) < 1e-12
    st = T.self_times(ops, 0.0, 2.5)
    assert abs(st["while.1"] - 0.3) < 1e-12 and abs(st["fusion.2"] - 0.8) < 1e-12
    busy, n = T.span_busy(ops, spans, "serve.prefill_step")
    assert n == 1 and abs(busy - 0.5) < 1e-12
    gaps = T.idle_gaps(ops, spans, 0.0, 2.5)
    want = {"serve.decode_step": 0.2, "serve.prefill_step": 0.2,
            T.SCHEDULER: 0.6}
    assert all(abs(gaps[k] - v) < 1e-12 for k, v in want.items()), gaps
    print("trace reduction on a hand-made trace: busy 1.5 s, kernel 0.4 s, "
          "self times, span busy, idle gaps by span -> ok")

    rec = json.loads((HERE / "testdata" / "trace_small.json").read_text())
    ops = [tuple(e) for e in rec["ops"]]
    spans = [tuple(e) for e in rec["spans"]]
    t0, t1 = T.window({"spans": spans})
    busy = T.busy_s(ops, t0, t1)
    brute = brute_busy(ops, t0, t1)
    kern = T.kernel_s(ops, r"_decode_paged_impl(\.\d+)?$", t0, t1)
    brute_k = sum(min(b, t1) - max(a, t0) for a, b, n in ops
                  if n.startswith("_decode_paged_impl"))
    selfs = sum(T.self_times(ops, t0, t1).values())
    gaps = sum(T.idle_gaps(ops, spans, t0, t1).values())
    ok = (abs(busy - brute) < 2e-6 * len(ops) and abs(kern - brute_k) < 1e-9
          and abs(selfs - busy) < 1e-6 and abs(gaps + busy - (t1 - t0)) < 1e-9)
    print(f"trace reduction on the recorded chip trace ({len(ops)} ops, "
          f"{len(spans)} spans, {t1 - t0:.4f} s): busy {busy:.6f} s vs "
          f"brute force {brute:.6f} s; paged-decode kernel {kern:.6f} s; "
          f"self times add to busy; idle + busy = window -> "
          f"{'ok' if ok else 'FAIL'}")
    assert ok


def check_stats() -> None:
    import stats

    rng = np.random.default_rng(1)
    xs = rng.normal(size=257).tolist()
    for q in (0, 5, 50, 95, 100):
        assert abs(stats.percentile(xs, q) - np.percentile(xs, q)) < 1e-12
    assert stats.percentile([3.0], 95) == 3.0
    stamps = {1: [0.0, 1.0, 3.0], 2: [2.5, 2.6]}
    gaps = sorted(round(g, 12) for g in stats.token_gaps(stamps, 0.5, 3.0))
    assert gaps == [0.1, 1.0], gaps
    assert stats.busy_union([(0, 2), (1, 3), (5, 6)], 0.5, 5.5) == 3.0
    assert abs(stats.spread([1, 2, 3, 4, 5, 6]) - (5.25 - 1.75) / 3.5) < 1e-12
    print("percentile = numpy's linear percentile at q 0/5/50/95/100; token "
          "gaps ending in the window; busy union; quartile spread -> ok")


def main() -> None:
    check_reference()
    check_trace()
    check_stats()
    print("selfcheck: all ok")


if __name__ == "__main__":
    main()
