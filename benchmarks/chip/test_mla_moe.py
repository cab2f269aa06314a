"""The ``mla_moe`` family and the cell ``deepseek-v2-lite.codegen`` at a
size a CPU can hold.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_mla_moe.py

The family's two segments are the program's own init layout, at smoke
and at published widths; its operation counts match a brute-force sum
and hand counts; the reference regenerates each layer as served; the two
readers of the expert layer read the kernel and the program's counters,
and nothing where either is missing.  The cell's files run through
``run.run_cell`` with the model cut to smoke widths (3 layers, 4 of 16
experts held, top-6), 4 slots and short requests, the Pallas kernels in
interpret mode: a sound run comes out correct under the cell's own
limits file, a run whose step leaves the cache unchanged or that holds
other experts than the reference does not.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run as R  # noqa: E402
import weights as W  # noqa: E402
from test_harness import PEAKS, SECONDS, devices, faulty  # noqa: E402

CELL = "deepseek-v2-lite.codegen"
SEED = 2**33 + 7
DEEPSEEK = json.loads((HERE / "configs" / "deepseek-v2-lite.json").read_text())
SMOKE = dict(n_experts=16, top_k=6)


def _smoke_model(cfg, held: int) -> dict:
    return dict(DEEPSEEK["model"], n_layers=cfg.n_layers,
                first_dense_layers=cfg.first_dense_layers,
                d_model=cfg.d_model, n_heads=cfg.n_heads,
                kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope,
                qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_hd,
                d_ff=cfg.d_ff, moe_d_ff=cfg.moe_d_ff, n_experts=cfg.n_experts,
                n_experts_held=held, top_k=cfg.top_k,
                n_shared_experts=cfg.n_shared_experts, vocab=cfg.vocab,
                eps=cfg.norm_eps)


def small_cell(held=(0, 4)) -> dict:
    """The cell's files at smoke widths; the reference holds experts 0-3,
    the program ``held``."""
    from repro.configs import get_config
    cf = json.loads(json.dumps(DEEPSEEK))
    mix = json.loads((HERE / "traffic" / "codegen.json").read_text())
    limits = json.loads((HERE / "limits" / f"{CELL}.json").read_text())
    s = get_config(cf["program"]["arch"], smoke=True, **SMOKE)
    cf["model"] = _smoke_model(s, 4)
    cf["program"] = {"arch": cf["program"]["arch"], "smoke": True,
                     "overrides": dict(cf["program"]["overrides"], **SMOKE,
                                       dtype="bfloat16",
                                       expert_range=list(held))}
    cf["deployment"].update(slots=4, s_max=256)
    mix.update(clients=4, requests=64)
    mix["output"].update(median=12, min=4, max=24)
    mix["prompt"].update(median=40, min=8, max=96)
    limits["sample_tokens_min"] = 10      # smoke windows serve short requests
    return {"name": CELL, "config": "deepseek-v2-lite", "traffic": "codegen",
            "chips": 1, "config_file": cf, "mix": mix, "limits": limits,
            "end_to_end": [{"name": n, "unit": "u"} for n in
                           ("output_tok_s", "itl_p95_ms", "setup_s")],
            "per_layer": [{"name": n, "unit": "%"} for n in
                          ("host_share", "mfu.output", "expert_block_fill")]}


def test_sound_run_is_correct():
    out = R.run_cell(small_cell(), 2**33 + 5, SECONDS, False, devices(),
                     peaks_all=PEAKS)
    assert out["correct"], out["checked"]
    assert out["checked"]["widest_gap"]["value"] < 0.1
    assert out["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "other_experts"])
def test_broken_run_is_not_correct(fault):
    if fault == "other_experts":          # the program holds experts 4-7
        cell, loop = small_cell(held=(4, 8)), None
    else:
        cell, loop = small_cell(), faulty(fault)
    out = R.run_cell(cell, 2**33 + 6, SECONDS, False, devices(),
                     loop_class=loop, peaks_all=PEAKS)
    assert not out["correct"], out["checked"]


def test_counters_reach_the_block_fill_reader():
    cell = small_cell()
    loop, setup_s = R.serve(cell, 2**33 + 11, SECONDS, False)
    ctx = R.Context(cell, loop, setup_s, PEAKS["cpu"], None)
    fill = R.reader("expert_block_fill")(ctx)
    assert ctx.delta("moe_rows") > 0 and 0 < fill <= 100
    assert fill == pytest.approx(100 * ctx.delta("moe_rows")
                                 / (128 * ctx.delta("moe_blocks")))


@pytest.mark.parametrize("smoke", [True, False])
def test_mla_moe_matches_the_programs_layout(smoke):
    """The family's two segments (the dense layer, then the expert layers
    holding 8 of 64 experts, or 4 of 16 at smoke widths) are the tree the
    program's init makes, leaf for leaf; at published widths shapes only,
    nothing drawn."""
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    from families import mla_moe
    held = 4 if smoke else 8
    over = SMOKE if smoke else {}
    cfg = get_config("deepseek-v2-lite-16b", smoke=smoke,
                     param_dtype="float32", expert_range=(0, held), **over)
    m = _smoke_model(cfg, held) if smoke else DEEPSEEK["model"]
    program = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(lambda: W.make_params(m, SEED, "float32"))
    W.check_layout(ours, program)
    counts = [c for c, _ in mla_moe.segments(m)]
    assert counts == [cfg.first_dense_layers,
                      cfg.n_layers - cfg.first_dense_layers]
    if not smoke:
        n = sum(a.size for a in jax.tree.leaves(ours))
        assert n == 3_110_989_312, n        # 6.22 GB in bfloat16


def test_mla_moe_counts_match_brute_force():
    """``span_flops`` (closed form) against a sum over tokens and layers
    of each product's 2 x weights and of attention over p + 1 keys; the
    decode and expert-GEMM costs at hand-counted values."""
    from families import mla_moe
    m = DEEPSEEK["model"]
    h, r, dn, dr, dv, d = (m["n_heads"], m["kv_lora_rank"], m["qk_nope_dim"],
                           m["qk_rope_dim"], m["v_head_dim"], m["d_model"])
    attn = (d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv
            + h * dv * d)
    dense = 3 * d * m["d_ff"]
    moe = (d * 64 + 6 * 8 / 64 * 3 * d * m["moe_d_ff"]
           + 3 * d * 2 * m["moe_d_ff"])
    for start, n in ((0, 1), (0, 32), (100, 7), (511, 1)):
        want = 0.0
        for p in range(start, start + n):
            for layer in range(m["n_layers"]):
                ffn = dense if layer < m["first_dense_layers"] else moe
                want += 2 * (attn + ffn) + 2 * h * (dn + dr + dv) * (p + 1)
        assert mla_moe.span_flops(m, start, n) == pytest.approx(want,
                                                                rel=1e-12)
    assert mla_moe.span_flops(m, 5, 0) == 0.0
    # one slot at 17 keys: two 16-token pages of 576 values, q 576 + out 512
    f, b = mla_moe.paged_decode_cost(m, [17], 16)
    assert f == 27 * 2 * 16 * (512 + 576) * 17
    assert b == 27 * 2 * (32 * 576 + 16 * (576 + 512))
    f, b = mla_moe.moe_gemm_cost(m, rows=96, steps=1)
    assert f == 2 * 96 * 3 * 2048 * 1408
    assert b == 2 * (26 * 8 * 3 * 2048 * 1408 + 96 * 3 * (2048 + 1408))


def test_mla_moe_reference_layers_are_the_served_layers():
    """The reference regenerates layer 0 (dense) and layer 2 (experts)
    alone, by index across the two segments, as served."""
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite-16b", smoke=True, **SMOKE)
    m = _smoke_model(cfg, 4)
    params = W.make_params(m, SEED, "bfloat16")
    dense, experts = params["segments"]
    r0 = W.reference_layer(m, SEED, 0, "bfloat16")
    r2 = W.reference_layer(m, SEED, 2, "bfloat16")
    assert np.array_equal(np.asarray(dense["mlp"]["w_up"][0], np.float32),
                          np.asarray(r0["mlp"]["w_up"]))
    for k1, k2 in (("attn", "w_uk"), ("moe", "w_down"), ("moe", "router")):
        assert np.array_equal(np.asarray(experts[k1][k2][1], np.float32),
                              np.asarray(r2[k1][k2]))


class _TraceCtx:
    """What the readers read: a synthetic trace of one chip, the counters
    at the window's ends, fixed steps."""

    def __init__(self, model, ops, rows, steps, counter=True, blocks=0):
        import families
        self.model, self.family = model, families.load(model)
        self.peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
        self.deployment = {"page": 16}
        self.trace = {"devices": {"/device:TPU:0": ops},
                      "spans": [(0.0, 0.0, "serve.decode_step"),
                                (1.0, 1.0, "serve.decode_step")]}
        self.c0 = {"moe_rows": 0, "moe_blocks": 0} if counter else {}
        self.c1 = {"moe_rows": rows, "moe_blocks": blocks} if counter else {}
        self._steps = steps

    def delta(self, key):
        return self.c1[key] - self.c0[key]

    def steps_in(self, kind=None):
        return [s for s in self._steps if kind is None or s[0] == kind]


def test_expert_readers_read_the_kernel_and_the_counters():
    roof = R.reader("grouped_matmul_roofline")
    fill = R.reader("expert_block_fill")
    from families import mla_moe
    m = DEEPSEEK["model"]
    steps = [("decode", 0.1, 0.2, [300] * 128, [299] * 128)]
    ops = [(0.10, 0.104, "_gmm_impl.33"), (0.105, 0.109, "_gmm_impl.34"),
           (0.11, 0.112, "fusion.9")]
    ctx = _TraceCtx(m, ops, rows=26 * 96, steps=steps, blocks=26 * 8)
    f, b = mla_moe.moe_gemm_cost(m, 26 * 96, 1)
    want = 100 * max(f / 197e12, b / 819e9) / 0.008
    assert roof(ctx) == pytest.approx(want, rel=1e-9)
    assert 0 < roof(ctx) <= 100
    assert fill(ctx) == pytest.approx(100 * 96 / (8 * 128))
    # a program that keeps no such counter (the parent), a family with no
    # experts, or no kernel in the trace: nothing, and no error
    for read in (roof, fill):
        assert read(_TraceCtx(m, ops, 0, steps, counter=False)) is None
    assert roof(_TraceCtx(m, ops[2:], 26 * 96, steps)) is None
    granite = json.loads((HERE / "configs" / "granite-34b.json").read_text())
    assert roof(_TraceCtx(granite["model"], ops, 5, steps)) is None
    assert fill(_TraceCtx(granite["model"], ops, 5, steps, blocks=1)) is None
