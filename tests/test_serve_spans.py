"""The serve loop's host instrumentation: the ``ServeStats`` phase
counters, the admit queue's wait, the live round count, and the
``jax.profiler`` spans that name each phase of a round on the trace's
clock (``serve.*`` disjoint and outside every step, ``step.*`` inside
one)."""

import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.runtime.serve_loop import PagedServeLoop, Request, ServeLoop

ARCH = "qwen3-4b"
ROUND_PHASES = {"serve.admit": "admit_s", "serve.prepare": "prepare_s",
                "serve.sample": "sample_s", "serve.emit": "emit_s"}
STEP_PHASES = {"step.dispatch": "dispatch_s", "step.wait": "wait_s",
               "step.copy": "copy_s"}
CALL = "test.step_call"

_MODELS = {}


def _model():
    if ARCH not in _MODELS:
        cfg = get_config(ARCH, smoke=True)
        m = build_model(cfg)
        _MODELS[ARCH] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[ARCH]


def _requests(vocab, n=5):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=5 + 3 * i),
                    max_new=4 + i) for i in range(n)]


def _recording(base):
    """``base`` with each round's live ``stats.rounds`` and each
    ``_step`` call recorded, the calls also as spans on the trace."""

    class Recording(base):
        def __init__(self, *a, **kw):
            self.seen_rounds = []
            super().__init__(*a, **kw)

        def _admit(self):
            self.seen_rounds.append(self.stats.rounds)
            super()._admit()

        def _step(self, tok, n_valid):
            with jax.profiler.TraceAnnotation(CALL):
                return super()._step(tok, n_valid)

    return Recording


LOOPS = {"paged": lambda cfg, m, p: _recording(PagedServeLoop)(
             cfg, m, p, batch_slots=2, s_max=48, page=4, chunk=8),
         "contiguous": lambda cfg, m, p: _recording(ServeLoop)(
             cfg, m, p, batch_slots=2, s_max=48, chunk=8)}


@pytest.mark.parametrize("kind", sorted(LOOPS))
def test_counters_read_live_and_add_up(kind):
    cfg, m, params = _model()
    loop = LOOPS[kind](cfg, m, params)
    t = time.perf_counter()
    loop.run(_requests(cfg.vocab))
    wall = time.perf_counter() - t
    st = loop.stats
    # rounds counts while run() runs; its final value is the round count
    assert loop.seen_rounds == list(range(1, st.rounds + 1))
    steps = st.prefill_steps + st.decode_steps
    assert steps > 0
    assert st.logits_bytes == steps * loop.b * cfg.vocab * 4
    assert st.queue_waits == st.admitted == 5
    phases = [getattr(st, c) for c in
              list(ROUND_PHASES.values()) + list(STEP_PHASES.values())]
    assert all(v >= 0 for v in phases)
    assert st.queue_wait_s >= 0
    # the seven phases are disjoint stretches of run()
    assert sum(phases) <= wall


def test_queue_waits_count_readmissions_after_preemption():
    """The page-exhaustion setup of test_paged_serve: the preempted
    request goes back to the admit queue and its second wait counts."""
    cfg, m, params = _model()
    reqs = [Request(rid=0, prompt=np.random.default_rng(1).integers(
                0, cfg.vocab, size=10), max_new=6),
            Request(rid=1, prompt=np.random.default_rng(2).integers(
                0, cfg.vocab, size=6), max_new=6)]
    tight = PagedServeLoop(cfg, m, params, batch_slots=2, s_max=16,
                           page=4, n_pages=6, prefix_reuse=False)
    tight.run(reqs)
    st = tight.stats
    assert st.preemptions >= 1
    assert st.queue_waits == st.admitted == len(reqs) + st.preemptions
    assert st.queue_wait_s > 0


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert files, "the profiler wrote no trace"
    pd = ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return sorted(out)


@pytest.mark.parametrize("kind", sorted(LOOPS))
def test_spans_on_the_trace(kind, tmp_path):
    cfg, m, params = _model()
    loop = LOOPS[kind](cfg, m, params)
    loop.run(_requests(cfg.vocab, n=2))        # compile outside the trace
    loop = LOOPS[kind](cfg, m, params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.run(_requests(cfg.vocab))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    rounds = [s for s in spans if s[2].startswith("serve.")]
    inner = [s for s in spans if s[2].startswith("step.")]
    calls = [s for s in spans if s[2] == CALL]
    assert {s[2] for s in rounds} == set(ROUND_PHASES)
    assert {s[2] for s in inner} == set(STEP_PHASES)
    st = loop.stats
    assert len(calls) == st.prefill_steps + st.decode_steps
    assert sum(s[2] == "serve.admit" for s in rounds) == st.rounds
    for a, b in zip(rounds, rounds[1:]):
        assert a[1] <= b[0], (a, b)            # pairwise disjoint
    for s in rounds:
        for c in calls:
            assert s[1] <= c[0] or c[1] <= s[0], (s, c)
    for s in inner:
        assert any(c[0] <= s[0] and s[1] <= c[1] for c in calls), s
