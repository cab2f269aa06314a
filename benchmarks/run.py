# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

    table1_perf       Table 1  (cycles, all benchmarks x HLS configs)
    table2_resources  Table 2  (buffer/channel resource analogue)
    table3_moms       Table 3  (MOMS + DRAM memory model subset)
    fig4_golden       Fig. 4   (overhead over the golden reference)
    kernel-bench      decoupled-kernel microbenches + RIF/capacity sweeps,
                      per-op tuned-vs-default and chase decoupled-vs-XLA
                      cells; writes BENCH_kernels.json at the repo root
                      (--smoke for the CI-sized subset)
    compile           repro.compile target grid: staged pipeline + compiled
                      kernel vs the simulator oracle (parity gated); writes
                      BENCH_compile.json (--smoke for the CI-sized subset)
    tune              autotune decoupling params, persist the config cache
    scale             N=1..64 tenants on one shared memory system
                      (throughput degradation + channel-occupancy traces;
                      --smoke for the CI-sized subset)
    engine-bench      event vs polling scheduler events/sec on the
                      N-tenant hashtable cell (--smoke gates the event
                      engine at >=5x on the contended N=96 cell)
    serve-bench       decoupled Access/Execute serving pipeline vs the
                      coupled legacy loop (batch_slots x prompt mixes x
                      archetypes, tokens/s + TTFT + channel occupancy;
                      --smoke gates >=5x on the mixed slots=8 cell) plus
                      the paged-KV open-loop cells: slots=64 seeded
                      Poisson/bursty arrival traces with prefix reuse,
                      TTFT p50/p95/p99 measured from arrival
    matrix            the declarative benchmark matrix (repro.bench):
                      runs EVERY registered cell of the sim/kernels/
                      compile/serve axes and writes one schema-validated
                      BENCH_<axis>.json per axis at the repo root;
                      gate a run against the committed baseline with
                      `python -m benchmarks.diff` (--smoke for CI scale;
                      --axes=serve,kernels restricts to those axes)

Run: PYTHONPATH=src python -m benchmarks.run [table1 table3 tune scale ...]
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def _csv(line: str) -> None:
    print(line, flush=True)


def main() -> None:
    flags = {a for a in sys.argv[1:] if a.startswith("-")}
    want = {a for a in sys.argv[1:] if not a.startswith("-")}
    if flags and not want:
        # a bare flag must not select the run-everything default
        print(f"error: flags {sorted(flags)} given without a benchmark "
              f"selector (e.g. 'scale --smoke')", file=sys.stderr)
        raise SystemExit(2)

    def on(name: str) -> bool:
        return not want or any(w in name for w in want)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    if on("table1"):
        from benchmarks import table1_perf
        table1_perf.run(_csv)
    if on("table2"):
        from benchmarks import table2_resources
        table2_resources.run(_csv)
    if on("table3"):
        from benchmarks import table3_moms
        table3_moms.run(_csv)
    if on("fig4"):
        from benchmarks import fig4_golden
        fig4_golden.run(_csv)
    if on("kernel-bench"):
        from benchmarks import kernel_bench
        kernel_bench.run(_csv, smoke="--smoke" in flags)
    if on("compile"):
        from benchmarks import compile_bench
        compile_bench.run(_csv, smoke="--smoke" in flags)
    if on("tune"):
        from benchmarks import tune
        tune.run(_csv)
    if on("scale"):
        from benchmarks import scale
        scale.run(_csv, smoke="--smoke" in flags)
    if on("engine-bench"):
        from benchmarks import engine_bench
        engine_bench.run(_csv, smoke="--smoke" in flags)
    if on("serve-bench"):
        from benchmarks import serve_bench
        serve_bench.run(_csv, smoke="--smoke" in flags)
    if want and on("matrix"):
        # explicit-only: the bare run-everything default already covers
        # each table once; matrix would re-run them all a second time
        from benchmarks import matrix
        axes = matrix.AXES
        for f in flags:
            # --axes=serve,kernels restricts the matrix to those axes
            # (e.g. the CI multi-device job re-runs only `serve`)
            if f.startswith("--axes="):
                axes = tuple(a for a in f[len("--axes="):].split(",") if a)
                unknown = set(axes) - set(matrix.AXES)
                if unknown:
                    print(f"error: unknown matrix axes {sorted(unknown)}; "
                          f"valid: {matrix.AXES}", file=sys.stderr)
                    raise SystemExit(2)
        matrix.run(_csv, smoke="--smoke" in flags, axes=axes)


if __name__ == "__main__":
    main()
