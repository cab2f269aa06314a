#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine with the chips the cell
asks for.  The cell (``BENCHMARK.json``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<mix>.json``);
the configuration names its model family (``families/<family>.py``: the
weights' shapes and the operation counts) and its plain reference; the
cell's metrics are readers in ``metrics/<metric>.py`` and its
correctness limits are in ``limits/<cell>.json``.  Nothing here names a
cell or a model's shapes: a later change adds either by adding those
files and entries.

The run makes the weights from ``--seed`` on the device, serves the mix
through the program's own ``PagedServeLoop`` with the Pallas kernels,
warms up every step shape and the mix's warm-up, then measures for
``--seconds``.  With ``--trace 1`` it also records a profiler trace of
the first ``TRACE_SECONDS`` of the window and reports the per-layer
metrics from it.  After the window the served tokens of a sample of
finished requests are compared with a plain float32 reference
(``reference.py``).  The last line of standard output is one JSON
object; the last lines of standard error are the numbers compared, each
beside its limit.  Without a TPU, or with fewer chips than the cell
asks for, or outside a checkout of the repository, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import families  # noqa: E402

TRACE_SECONDS = 10.0


class Failed(Exception):
    """A run that can give no result."""


class WindowClosed(Exception):
    """Raised by the loop at the first step that starts after the window."""


def fail(msg: str) -> None:
    print(f"benchmark: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def steal_s() -> Optional[float]:
    """CPU seconds the hypervisor took from this machine, over all its
    cores (``/proc/stat``), or None where that is not kept."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def keep_freed_memory() -> None:
    """Fix glibc's allocator so that the heap keeps what it frees.

    A step's logits (granite's 25 MB) are fetched into a fresh heap
    buffer.  With glibc's defaults the heap's top is sometimes given back
    when that buffer is freed, depending on how the process's heap lies,
    and the next buffer then pays a first touch of every page: on a TPU
    v5e host the decode step's argmax reads 26-30 ms instead of ~3 ms for
    the whole of such a process.  Fixed thresholds make every process
    keep its pages."""
    import ctypes
    M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD = -1, -2, -3
    mallopt = ctypes.CDLL(None).mallopt
    for param, value in ((M_MMAP_THRESHOLD, 32 << 20),
                         (M_TRIM_THRESHOLD, 1 << 30),
                         (M_TOP_PAD, 256 << 20)):
        if mallopt(param, value) != 1:
            raise Failed(f"mallopt({param}, {value}) refused")


def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise Failed(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Dict:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failed(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    cell["config_file"] = load_json(HERE / "configs" / f"{cell['config']}.json")
    try:
        families.load(cell["config_file"]["model"])
    except LookupError as e:
        raise Failed(f"configs/{cell['config']}.json: {e}") from None
    cell["mix"] = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = load_json(HERE / "limits" / f"{name}.json")
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_loop_class():
    """The program's ``PagedServeLoop`` with the benchmark's seams: step
    timing and spans, a stamp per emitted token, the closed-loop client
    and the window."""
    import jax
    import numpy as np
    from repro.runtime import serve_loop as sl

    class BenchLoop(sl.PagedServeLoop):
        def bench_init(self, traffic, mix: Dict, seconds: float,
                       trace_dir: Optional[Path]):
            self.traffic = traffic
            self.mix = mix
            self.seconds = seconds
            self.trace_dir = trace_dir
            self.n_step = 0
            self.t0 = self.t1 = self.deadline = None
            self.trace_t = None              # [start, end] of the trace
            self.snap: Dict[str, Dict] = {}
            self.steps: List[tuple] = []     # (kind, start, end, lens, starts)
            self.stamps: Dict[int, List[float]] = {}
            self.submitted: Dict[int, float] = {}
            self.finished: Dict[int, tuple] = {}
            self.prompts: Dict[int, np.ndarray] = {}
            self.held: List[int] = []        # pool pages held, per round

        # -- traffic ---------------------------------------------------------

        def new_request(self):
            r = self.traffic.next()
            self.prompts[r["rid"]] = r["prompt"]
            self.submitted[r["rid"]] = time.perf_counter()
            return sl.Request(rid=r["rid"], prompt=r["prompt"],
                              max_new=r["max_new"])

        def _finish(self, slot, results):
            req = self.active[slot]
            super()._finish(slot, results)
            self.finished[req.rid] = (time.perf_counter(), list(req.out))
            if self.mix["loop"] == "closed":
                nxt = self.new_request()
                if not self.admit_q.push(nxt):
                    self._overflow.append(nxt)

        def _keep_logits(self, rid, row):
            self.stamps.setdefault(rid, []).append(time.perf_counter())

        # -- window ----------------------------------------------------------

        def _warm(self) -> bool:
            w = self.mix["warmup"]
            if "steps" in w:
                return self.n_step >= w["steps"]
            return len(self.finished) >= w["finished"]

        def snapshot(self, key: str) -> None:
            self.snap[key] = dict(vars(self.stats), ttft=None)
            self.snap[key]["t"] = time.perf_counter()
            self.snap[key]["steal"] = steal_s()

        def _admit(self):
            self._round()
            super()._admit()

        def _round(self) -> None:
            """Open and close the window and the trace, at the start of a
            round (admission, a decode step, a prefill step), so that the
            window holds whole rounds whatever their length."""
            now = time.perf_counter()
            if self.t0 is None:
                if self._warm():
                    self.t0 = now
                    self.deadline = now + self.seconds
                    self.snapshot("start")
                    if self.trace_dir is not None:
                        jax.profiler.start_trace(str(self.trace_dir))
                        self.trace_t = [time.perf_counter(), None]
                return
            self.held.append(self.alloc.n_pages - 1 - self.alloc.free_count)
            if (self.trace_t and self.trace_t[1] is None
                    and now >= self.t0 + min(TRACE_SECONDS, self.seconds)):
                jax.block_until_ready(self.cache)
                self.trace_t[1] = time.perf_counter()
                self.snapshot("trace_end")
                jax.profiler.stop_trace()
                # writing the trace out is no part of the window
                now = time.perf_counter()
                self.deadline += now - self.trace_t[1]
            if now >= self.deadline:
                self.t1 = now
                self.snapshot("end")
                if self.trace_t and self.trace_t[1] is None:
                    jax.block_until_ready(self.cache)
                    self.trace_t[1] = time.perf_counter()
                    self.snap["trace_end"] = self.snap["end"]
                    jax.profiler.stop_trace()
                raise WindowClosed

        def _step(self, tok, n_valid):
            self.n_step += 1
            kind = "prefill" if tok.shape[1] > 1 else "decode"
            valid = np.asarray(n_valid) > 0
            lens = (np.asarray(self.pos)[valid] + np.asarray(n_valid)[valid])
            starts = np.asarray(self.pos)[valid]
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"serve.{kind}_step"):
                out = super()._step(tok, n_valid)
            self.steps.append((kind, a, time.perf_counter(), lens.tolist(),
                               starts.tolist()))
            return out

    return BenchLoop


def require_chip(chips: int):
    import jax
    from repro.kernels.common import resolve_interpret
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        raise Failed(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise Failed(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    if resolve_interpret(None) is not False:
        raise Failed("kernels would run in interpret mode on the chip")
    return devices


def build(cell: Dict, seed: int):
    """The program's config and bundle, and the benchmark's weights."""
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    import weights as W

    cf = cell["config_file"]
    prog = cf["program"]
    cfg = get_config(prog["arch"], smoke=prog.get("smoke", False),
                     **prog.get("overrides", {}))
    bundle = build_model(cfg)
    params = W.make_params(cf["model"], seed, cf["deployment"]["dtype"])
    W.check_layout(params, jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    jax.block_until_ready(params)
    return cfg, bundle, params


def warm_shapes(loop) -> None:
    """Run every program the window drives once, on no valid token: both
    step widths, the slot reset and the page copy."""
    import jax
    import jax.numpy as jnp
    b = loop.b
    # each program donates the cache: take the returned one each time
    for c in (loop.chunk, 1):
        _, loop.cache = loop._fwd(
            loop.params, loop.cache, jnp.zeros((b, c), jnp.int32),
            jnp.asarray(loop.pos), jnp.zeros(b, jnp.int32),
            jnp.asarray(loop.table))
    loop.cache = loop._reset_paged(loop.cache, jnp.ones(b, bool),
                                   jnp.zeros(b, jnp.int32))
    loop.cache = loop._copy(loop.cache, jnp.int32(0), jnp.int32(0))
    jax.block_until_ready(loop.cache)


def serve(cell: Dict, seed: int, seconds: float, trace: bool,
          loop_class=None):
    """Build, warm up and serve the window; returns (loop, setup_s)."""
    import jax
    from generator import Traffic

    cf, mix = cell["config_file"], cell["mix"]
    dep = cf["deployment"]
    cfg, bundle, params = build(cell, seed)
    loop_class = loop_class or make_loop_class()
    loop = loop_class(cfg, bundle, params, dep["slots"], dep["s_max"],
                      chunk=dep["chunk"], page=dep["page"],
                      n_pages=dep.get("n_pages"))
    traffic = Traffic(mix, seed, cf["model"]["vocab"], dep["page"])
    if traffic.longest_fill() > dep["s_max"]:
        raise Failed(f"the mix needs {traffic.longest_fill()} positions, "
                     f"s_max is {dep['s_max']}")
    trace_dir = None
    if trace:
        trace_dir = ROOT / "chiprun_out" / "traces" / f"{cell['name']}-{seed}"
        trace_dir.mkdir(parents=True, exist_ok=True)
    loop.bench_init(traffic, mix, seconds, trace_dir)
    warm_shapes(loop)
    # every trace or compile that happens inside the window is logged
    import jax.monitoring as mon

    def heard(event, secs, **_):
        if (event.startswith("/jax/core/compile/") and loop.t0 is not None
                and loop.t1 is None):
            loop.compiles.append((event, secs))

    loop.compiles = []
    mon.register_event_duration_secs_listener(heard)
    # and every collection of the garbage collector: (seconds, generation)
    loop.gcs, gc_t = [], []

    def collected(phase, info):
        if phase == "start":
            gc_t[:] = [time.perf_counter()]
        elif gc_t and loop.t0 is not None and loop.t1 is None:
            loop.gcs.append((time.perf_counter() - gc_t[0],
                             info["generation"]))

    gc.callbacks.append(collected)
    if mix["loop"] == "open":
        times = traffic.arrivals()
        first = [loop.new_request() for _ in times]
        for req, t in zip(first, times):
            req.t_arrival = t
        base = time.perf_counter()
        loop.submitted = {r.rid: base + r.t_arrival for r in first}
    else:
        first = [loop.new_request() for _ in range(mix["clients"])]
    try:
        loop.run(first, max_rounds=10**9)
    except WindowClosed:
        pass
    else:
        raise Failed("every request finished before the window closed: "
                     "the mix is too small for --seconds")
    finally:
        mon.unregister_event_duration_listener(heard)
        gc.callbacks.remove(collected)
    jax.block_until_ready(loop.cache)
    setup_s = loop.t0 - T_PROCESS
    return loop, setup_s


def read_trace(loop) -> Optional[Dict]:
    import glob
    import reduce_trace as T
    if loop.trace_dir is None:
        return None
    files = glob.glob(str(loop.trace_dir / "**" / "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise Failed("the profiler wrote no trace")
    tr = T.load(max(files, key=os.path.getmtime))
    if not tr["spans"] or not tr["devices"]:
        raise Failed("the trace holds no serve span or no device")
    return tr


class Context:
    """What the metric readers read.  ``t0``/``t1`` bound the window the
    metrics cover (with a trace: the traced part), ``c0``/``c1`` are the
    program's counters (``ServeStats``) at those times."""

    def __init__(self, cell, loop, setup_s, peaks, tr):
        self.cell = cell
        self.model = cell["config_file"]["model"]
        self.family = families.load(self.model)
        self.deployment = cell["config_file"]["deployment"]
        self.peaks = peaks
        self.setup_s = setup_s
        self.trace = tr
        self.steps = loop.steps
        self.stamps = loop.stamps
        self.submitted = loop.submitted
        self.finished = loop.finished
        self.c0 = loop.snap["start"]
        self.c1 = loop.snap["trace_end" if tr is not None else "end"]
        self.t0, self.t1 = self.c0["t"], self.c1["t"]
        self.window_s = self.t1 - self.t0

    def delta(self, key: str) -> int:
        return self.c1[key] - self.c0[key]

    def steps_in(self, kind: Optional[str] = None):
        return [s for s in self.steps if self.t0 <= s[1] < self.t1
                and (kind is None or s[0] == kind)]


def sampled(cell: Dict, loop, seed: int):
    """The finished requests and the sample compared: (finished, seqs,
    rows, chosen), ``chosen`` being each sampled request's served tokens."""
    import check
    lim = cell["limits"]
    fin = {r: (loop.prompts[r], out) for r, (t, out) in loop.finished.items()
           if t <= loop.t1}
    rids = check.pick_sample(fin, seed, lim["sample_tokens"],
                             lim["sample_requests"])
    seqs, rows, chosen = [], [], []
    for r in rids:
        seq, row = check.teacher_forced(*fin[r])
        seqs.append(seq)
        rows.append(row)
        chosen.append(fin[r][1])
    return fin, seqs, rows, chosen


def reference_logits(cell: Dict, seed: int, seqs, rows, control=False):
    """The configuration's plain reference (the module its ``reference``
    names) at the sampled positions."""
    import jax
    cf = cell["config_file"]
    R = importlib.import_module(Path(cf["reference"]).stem)
    with jax.default_matmul_precision("highest"):
        return R.logits_at(cf["model"], cf["deployment"]["dtype"], seed,
                           seqs, rows, control=control)


def correctness(cell: Dict, loop, seed: int, control: bool = False
                ) -> Dict[str, Dict]:
    """Reference over a sample of finished requests; returns the numbers
    compared, each with its limit, under ``"program"``.  With ``control``
    also under ``"control"``: the same verdict on the tokens that the
    reference computed in float8 puts first at the same positions, in
    the program's place."""
    import check
    import numpy as np

    lim = cell["limits"]
    vocab = cell["config_file"]["model"]["vocab"]
    fin, seqs, rows, chosen = sampled(cell, loop, seed)
    wrong = sum(1 for r, (_, out) in fin.items()
                if len(out) != loop.traffic.outputs[r]
                or not all(0 <= x < vocab for x in out))
    n_tok = sum(len(c) for c in chosen)
    log(f"check: {len(chosen)} requests ({n_tok} served tokens) of "
        f"{len(fin)} finished, longest "
        f"{max((len(c) for c in chosen), default=0)}")
    ref = reference_logits(cell, seed, seqs, rows) if chosen else []
    picks = {"program": chosen}
    if control and chosen:
        low = reference_logits(cell, seed, seqs, rows, control=True)
        picks["control"] = [np.argmax(lw, -1) for lw in low]
    out = {}
    for who, toks in picks.items():
        widest = max((float(check.gaps(lg, c).max())
                      for lg, c in zip(ref, toks)), default=float("inf"))
        out[who] = {
            "widest_gap": {"value": widest, "limit": lim["widest_gap"]},
            "tokens_compared": {"value": n_tok,
                                "limit": lim["sample_tokens_min"]},
            "wrong_length_or_id": {"value": wrong, "limit": 0},
        }
    return out


def is_correct(checked: Dict) -> bool:
    return (checked["widest_gap"]["value"] <= checked["widest_gap"]["limit"]
            and checked["tokens_compared"]["value"]
            >= checked["tokens_compared"]["limit"]
            and checked["wrong_length_or_id"]["value"] == 0)


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             devices, loop_class=None, peaks_all=None) -> Dict:
    """The whole run after the device check; returns the result line."""
    import statistics
    import stats

    peaks_all = peaks_all or load_json(HERE / "peaks.json")
    dev = devices[0]
    if dev.device_kind not in peaks_all:
        raise Failed(f"no peaks for device kind {dev.device_kind!r} in "
                     "peaks.json")
    loop, setup_s = serve(cell, seed, seconds, trace, loop_class)
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices[:cell["chips"]])
    tr = read_trace(loop)
    if tr is not None:
        import shutil
        shutil.rmtree(loop.trace_dir, ignore_errors=True)
    ctx = Context(cell, loop, setup_s, peaks_all[dev.device_kind], tr)
    full = Context(cell, loop, setup_s, peaks_all[dev.device_kind], None)

    # what every run logs besides its metrics
    ttft = [loop.stamps[r][0] - loop.submitted[r] for r in loop.stamps
            if full.t0 <= loop.stamps[r][0] < full.t1]
    started = sum(1 for t in loop.submitted.values() if full.t0 <= t < full.t1)
    served = sum(1 for r, ts in loop.stamps.items()
                 if any(full.t0 <= t < full.t1 for t in ts)
                 or full.t0 <= loop.submitted[r] < full.t1)
    done = sum(1 for t, _ in loop.finished.values() if full.t0 <= t < full.t1)
    log(f"window: {full.window_s:.3f} s, setup {setup_s:.3f} s, "
        f"{len(full.steps_in('prefill'))} prefill + "
        f"{len(full.steps_in('decode'))} decode steps; requests started "
        f"{started}, finished {done}; prompt tokens prefilled "
        f"{full.delta('prefill_tokens')}, adopted "
        f"{full.delta('prefix_tokens_reused')}; tokens emitted "
        f"{sum(1 for ts in loop.stamps.values() for t in ts if full.t0 <= t < full.t1)}; "
        f"preemptions {full.delta('preemptions')}")
    win = full.steps_in()
    pauses = [b[1] - a[2] for a, b in zip(win, win[1:])]
    longest = max(win, key=lambda s: s[2] - s[1], default=None)
    steal = [full.c0["steal"], full.c1["steal"]]
    log(f"inside the window: {len(loop.compiles)} trace/compile events "
        f"({sum(t for _, t in loop.compiles):.3f} s); longest step "
        + (f"{longest[2] - longest[1]:.4f} s ({longest[0]}, at "
           f"+{longest[1] - full.t0:.2f} s)" if longest else "none")
        + f"; longest pause between steps {max(pauses, default=0):.4f} s; "
        f"{len(loop.gcs)} collections, longest "
        f"{max(loop.gcs, default=(0.0, 0))} (s, generation); steal "
        + (f"{steal[1] - steal[0]:.2f} s" if None not in steal else "n/a"))
    if loop.held:
        held = statistics.fmean(loop.held)
        log(f"pool: {held:.1f} of {loop.alloc.n_pages - 1} pages held on "
            f"average over the window's rounds "
            f"({100 * held / (loop.alloc.n_pages - 1):.2f}%), at most "
            f"{max(loop.held)}")
    if ttft:
        log(f"ttft: n={len(ttft)} p50={stats.percentile(ttft, 50):.4f} s "
            f"p95={stats.percentile(ttft, 95):.4f} s")

    metrics: Dict[str, Dict] = {}
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    source = ctx if trace else full
    for m in wanted:
        if m["name"] == "setup_s":
            val = setup_s
        else:
            val = reader(m["name"])(source)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak}
    out = {"attempted": served, "failed": 0, "metrics": metrics,
           "device": device}
    if tr is not None:
        import reduce_trace as T
        t0, t1 = T.window(tr)
        ops = list(tr["devices"].values())
        device["busy_s"] = statistics.fmean(T.busy_s(o, t0, t1) for o in ops)
        device["window_s"] = t1 - t0
        selfs, gaps = {}, {}
        for o in ops:
            for k, v in T.self_times(o, t0, t1).items():
                selfs[k] = selfs.get(k, 0.0) + v / len(ops)
            for k, v in T.idle_gaps(o, tr["spans"], t0, t1).items():
                gaps[k] = gaps.get(k, 0.0) + v / len(ops)
        out["breakdown"] = {"device_ops": T.top(selfs), "idle_gaps": T.top(gaps)}

    # the reference runs once the program's state is freed
    del loop.params, loop.cache, loop.bundle
    gc.collect()
    checked = correctness(cell, loop, seed)["program"]
    out["correct"] = is_correct(checked)
    out["failed"] = checked["wrong_length_or_id"]["value"]
    out["checked"] = checked
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        keep_freed_memory()
        if not (ROOT / "src" / "repro").is_dir():
            raise Failed("no src/repro in this checkout: run from the root "
                         "of a checkout of the repository")
        cell = load_cell(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        # tuned knobs come only from this file of the repository, or none
        os.environ["REPRO_TUNE_CACHE"] = str(
            ROOT / "benchmarks" / "tune_cache_tpu.json")
        # the compilation cache lives at a fixed path inside the checkout
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        devices = require_chip(cell["chips"])
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices)
    except Failed as e:
        fail(str(e))
    for name, v in out["checked"].items():
        log(f"checked: {name} = {v['value']!r} (limit {v['limit']!r})")
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checked"] = out["checked"]
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
