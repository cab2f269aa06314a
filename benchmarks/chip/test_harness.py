"""Tests of the benchmark at a size a CPU can hold.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

They skip the harness's look for a chip and drive the rest of a run
(weights, warm-up, window, metrics, the comparison with the reference)
on a two-layer model of each configuration's kind, with the Pallas
kernels in interpret mode.  A sound run must come out correct; a run
with the timed path broken underneath must not, once for each fault a
one-chip serving cell can have.  The control (the reference in float8
put in the program's place) must come out not correct under the cell's
own limits file, and read at least three times what the sound program
reads.
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run as R  # noqa: E402

SECONDS = 3.0
# the control's windows are longer: its widest gap is a maximum over the
# tokens compared, and a 3 s window on a slow host compares too few (46
# tokens read 0.28 at qwen3-4b's 36 smoke layers, ~150 read 0.56-0.78)
CONTROL_SECONDS = 8.0
PEAKS = {"cpu": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 1e10}}

# a prompt of one of a few documents and a question of its own, on the
# qwen3-4b configuration: the generator's documents and the prefix
# cache, which no cell of BENCHMARK.json drives
DOCS = {"loop": "closed", "clients": 4, "requests": 4096,
        "documents": {"count": 6, "min": 32, "max": 96, "zipf_s": 1.1,
                      "question_min": 8, "question_max": 24},
        "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
        "warmup": {"steps": 6}}
# the documents mix's own limits, those it was judged under before the
# qwen3-4b configuration had a cell of its own (granite's)
DOCS_LIMITS = {"widest_gap": 0.15, "sample_tokens": 320,
               "sample_requests": 8, "sample_tokens_min": 200}


def small_cell(config: str, traffic: str, real_depth: bool = False) -> dict:
    """A cell's files with the model cut to smoke widths (and depth,
    unless ``real_depth``), 4 slots and short requests; the limits are
    the cell's own file's.  The mix ``docs`` is the test's own ``DOCS``,
    judged under its own ``DOCS_LIMITS``."""
    cf = json.loads((HERE / "configs" / f"{config}.json").read_text())
    if traffic == "docs":
        mix, limits = copy.deepcopy(DOCS), dict(DOCS_LIMITS)
    else:
        mix = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
        limits = json.loads(
            (HERE / "limits" / f"{config}.{traffic}.json").read_text())
    from repro.configs import get_config
    arch = cf["program"]["arch"]
    s = get_config(arch, smoke=True)
    layers = cf["model"]["n_layers"] if real_depth else s.n_layers
    cf["model"].update(n_layers=layers, d_model=s.d_model,
                       n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
                       head_dim=s.hd, d_ff=s.d_ff, vocab=s.vocab,
                       eps=s.norm_eps)
    cf["program"] = {"arch": arch, "smoke": True,
                     "overrides": dict(cf["program"]["overrides"],
                                       n_layers=layers, dtype="bfloat16")}
    cf["deployment"].update(slots=4, s_max=256)
    mix.update(clients=4, requests=max(mix["requests"], 64))
    mix["output"].update(median=12, min=4, max=24)
    if "prompt" in mix:
        mix["prompt"].update(median=40, min=8, max=96)
    limits["sample_tokens_min"] = 10      # smoke windows serve short requests
    return {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1, "config_file": cf, "mix": mix,
            "limits": limits,
            "end_to_end": [{"name": n, "unit": "u"} for n in
                           ("output_tok_s", "prompt_tok_s", "itl_p95_ms",
                            "setup_s")],
            "per_layer": [{"name": n, "unit": "%"} for n in
                          ("host_share", "mfu.output")]}


def devices():
    import jax
    return jax.devices()


CELLS = [("granite-34b", "codegen"), ("qwen3-4b", "azure_code")]
DOCS_CELL = ("qwen3-4b", "docs")


@pytest.mark.parametrize("config,traffic", CELLS + [DOCS_CELL])
def test_sound_run_is_correct(config, traffic):
    out = R.run_cell(small_cell(config, traffic), 2**33 + 5, SECONDS, False,
                     devices(), peaks_all=PEAKS)
    assert out["correct"], out["checked"]
    assert out["checked"]["widest_gap"]["value"] < 0.25
    assert "itl_p95_ms" in out["metrics"]
    assert out["metrics"]["prompt_tok_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0


def faulty(kind: str):
    """The benchmark's loop with the timed path broken underneath."""
    import jax
    import numpy as np
    base = R.make_loop_class()

    class Broken(base):
        def _make_cache(self):
            super()._make_cache()
            if kind == "state_unchanged":
                step = jax.jit(self.bundle.prefill_paged)   # no donation
                self._fwd = lambda p, cache, *a: (step(p, cache, *a)[0],
                                                  cache)

        def _step(self, tok, n_valid):
            logits = super()._step(tok, n_valid)
            if kind == "half_batch":
                h = self.b // 2
                logits = logits.copy()
                logits[h:] = logits[:h]
            if kind == "token_altered" and self.n_step % 5 == 0:
                # one slot's token, a different slot each time
                slot = (self.n_step // 5) % self.b
                logits = logits.copy()
                logits[slot, np.argmax(logits[slot])] = -1e9
            return logits

    return Broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
@pytest.mark.parametrize("config,traffic", CELLS + [DOCS_CELL])
def test_broken_run_is_not_correct(fault, config, traffic):
    out = R.run_cell(small_cell(config, traffic), 2**33 + 6, SECONDS, False,
                     devices(), loop_class=faulty(fault), peaks_all=PEAKS)
    assert not out["correct"], out["checked"]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_control_is_not_correct(config, traffic):
    """The control, judged in the program's place by ``run.correctness``
    under the cell's own limits file, comes out not correct on every
    seed, and the program correct; its widest gap is three times the
    program's or more.  At the cell's own depth: float8's error grows
    with it, and two layers read under the limit."""
    import control
    rows = control.verdicts(small_cell(config, traffic, real_depth=True),
                            [11, 12, 13], CONTROL_SECONDS)
    for r in rows:
        assert r["program"]["correct"] and not r["control"]["correct"], r
    prog = max(r["program"]["widest_gap"] for r in rows)
    ctl = min(r["control"]["widest_gap"] for r in rows)
    assert ctl >= 3 * prog, (prog, ctl)


@pytest.mark.parametrize("arrivals", [{"kind": "poisson", "rate_per_s": 20.0},
                                      {"kind": "bursty", "burst": 4,
                                       "gap_s": 0.2}])
def test_open_loop_mix_runs(arrivals):
    """An open-loop mix is data only: arrivals on a schedule, TTFT from
    each request's arrival."""
    cell = small_cell("granite-34b", "codegen")
    cell["mix"].update(loop="open", arrivals=arrivals, requests=400,
                       warmup={"steps": 6})
    out = R.run_cell(cell, 2**33 + 9, SECONDS, False, devices(),
                     peaks_all=PEAKS)
    assert out["correct"], out["checked"]
    assert out["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("config,traffic", CELLS)
def test_every_metric_reader_reads_a_window(config, traffic):
    """Every metric of BENCHMARK.json has a reader; host-side ones give a
    number on a window, trace-side ones give nothing without a trace."""
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    cell = small_cell(config, traffic)
    loop, setup_s = R.serve(cell, 2**33 + 11, SECONDS, False)
    ctx = R.Context(cell, loop, setup_s, PEAKS["cpu"], None)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "setup_s":
            continue
        val = R.reader(m["name"])(ctx)
        if m["source"] == "device_trace":
            assert val is None, m["name"]
        else:
            assert val is not None and val >= 0, m["name"]
            if m["unit"] == "%":
                assert val <= 100, (m["name"], val)


def test_allocator_keeps_freed_memory():
    """The run fixes glibc's thresholds; a 25 MB buffer freed and taken
    again then comes back at the same address, its pages kept."""
    R.keep_freed_memory()
    a = np.empty(25 << 20, np.uint8)
    a[:] = 1
    addr = a.ctypes.data
    del a
    b = np.empty(25 << 20, np.uint8)
    assert b.ctypes.data == addr
