#!/usr/bin/env python3
"""Readings that set a cell's ``widest_gap`` limit, and the control's
verdict under the limit the cell has.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

For each seed, one process serves the cell's window as a benchmark run
does (same weights, warm-up, window and sample) and then gives two
verdicts of ``run.correctness``, with the cell's own limits file
(``limits/<cell>.json``):

- program: the served tokens, as a benchmark run judges them;
- control: the tokens that the reference computed in float8
  (``reference.py``, ``control=True``) puts first at the same positions,
  judged in the program's place.

Each number compared is the widest gap below the float32 reference's
best.  The limit goes above the largest program reading and below the
smallest control reading; the control has to come out not correct.  The
benchmark's own runs do not run this.  It prints one JSON line per seed
and a summary line, and exits non-zero where a program verdict is not
correct or a control verdict is.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from typing import Dict, List, Sequence

import run as R


def verdicts(cell, seeds: Sequence[int], seconds: float) -> List[Dict]:
    """Per seed: ``{"seed", "program", "control"}``, each verdict the
    numbers compared with their limits and ``correct``."""
    out = []
    for seed in seeds:
        loop, _ = R.serve(cell, seed, seconds, False)
        del loop.params, loop.cache, loop.bundle
        gc.collect()
        checked = R.correctness(cell, loop, seed, control=True)
        if "control" not in checked:
            raise R.Failed(f"seed {seed}: no request finished")
        row = {"seed": seed}
        for who, c in checked.items():
            row[who] = {k: v["value"] for k, v in c.items()}
            row[who]["correct"] = R.is_correct(c)
        print(json.dumps(row), flush=True)
        out.append(row)
        del loop
        gc.collect()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import os
    try:
        cell = R.load_cell(args.workload)
        sys.path.insert(0, str(R.ROOT / "src"))
        os.environ["REPRO_TUNE_CACHE"] = str(
            R.ROOT / "benchmarks" / "tune_cache_tpu.json")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.ROOT / ".jax_cache")
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        R.require_chip(cell["chips"])
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        seeds = [int(s) for s in args.seeds.split(",")]
        rows = verdicts(cell, seeds, args.seconds)
    except R.Failed as e:
        R.fail(str(e))
    prog = [r["program"]["widest_gap"] for r in rows]
    ctl = [r["control"]["widest_gap"] for r in rows]
    ok = (all(r["program"]["correct"] for r in rows)
          and not any(r["control"]["correct"] for r in rows))
    print(json.dumps({"workload": args.workload,
                      "limit": cell["limits"]["widest_gap"],
                      "program_max": max(prog), "control_min": min(ctl),
                      "program": prog, "control": ctl,
                      "program_correct_control_not": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
