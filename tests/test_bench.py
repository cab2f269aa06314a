"""Tests for the ``repro.bench`` matrix/schema/diff layer.

Four concerns:

  * **schema** — valid reports pass; each way a report can lie (folded
    cold-without-warm timing, unknown coords, duplicate names, wrong
    version) is rejected with the offending path named;
  * **diff discipline** — on synthetic reports: cycle changes fail in
    *both* directions, wall-clock gates only past the percent band,
    removed cells fail, new cells are notes, allowlisting downgrades a
    failure without hiding it, mode mismatches short-circuit;
  * **committed artifacts** — the baselines under ``benchmarks/baseline``
    must validate against the live schema and self-diff clean (the CI
    gate's no-op case), and every axis declared by ``benchmarks.matrix``
    must have one;
  * **enumeration** — the matrix declares every cell without executing
    any (cells are closures), and the registry rejects dup names/bad
    coords up front.
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))  # benchmarks.* is a root package

from repro.bench import (BenchContext, Cell, CellResult, Timing, build_report,
                         cell_csv, check_cells, coords, diff_reports,
                         parse_allowlist, regressions)
from repro.bench.schema import SchemaError, schema_problems, validate_report


def _report(cells=None, axis="sim", smoke=True):
    """A minimal schema-valid report to mutate in tests."""
    if cells is None:
        cells = [_cell("table1/binsearch/rhls_dec", cycles=3104),
                 _cell("kernel/gather/tuned", cycles=None, us_cold=900.0,
                       us_warm=120.0, tuned=True),
                 _cell("table2/binsearch/rhls_dec", cycles=None,
                       derived={"channels": 2, "note": "x"})]
    return {"schema": 2, "axis": axis, "smoke": smoke,
            "meta": {"git_sha": "deadbeef", "backend": "cpu", "seed": 0,
                     "python": "3.11.0"},
            "cells": cells}


def _cell(name, *, cycles=3104, us_cold=None, us_warm=None, status="ok",
          derived=None, tuned=None, replay=None):
    out = {"name": name, "group": name.split("/")[0],
           "coords": coords(name.split("/")[1], "sim", tuned=tuned),
           "status": status, "cycles": cycles, "us_cold": us_cold,
           "us_warm": us_warm, "derived": derived or {}}
    if replay is not None:
        out["replay"] = replay
    return out


# -- schema -------------------------------------------------------------------


def test_valid_report_passes():
    assert schema_problems(_report()) == []
    validate_report(_report())


@pytest.mark.parametrize("mutate,needle", [
    (lambda r: r.update(schema=1), "schema"),
    (lambda r: r.update(axis=""), "axis"),
    (lambda r: r.update(smoke="yes"), "smoke"),
    (lambda r: r["meta"].pop("git_sha"), "git_sha"),
    (lambda r: r["meta"].update(seed="0"), "seed"),
    (lambda r: r.update(cells=[]), "cells"),
    (lambda r: r["cells"].append(dict(r["cells"][0])), "duplicate"),
    (lambda r: r["cells"][0].update(status="crashed"), "status"),
    (lambda r: r["cells"][0].update(cycles=-1), "cycles"),
    (lambda r: r["cells"][0]["coords"].update(extra=1), "coords"),
    (lambda r: r["cells"][0]["coords"].pop("tenants"), "coords"),
    (lambda r: r["cells"][0].update(derived={"a": [1]}), "derived"),
    # the old folded-JIT shape: one timing number pretending to be both
    (lambda r: r["cells"][0].update(cycles=None, us_cold=5.0,
                                    us_warm=None, derived={}), "us_cold"),
    # an ok cell with no data at all measured nothing
    (lambda r: r["cells"][0].update(cycles=None, derived={}), "ok cell"),
])
def test_schema_rejects(mutate, needle):
    report = _report()
    mutate(report)
    problems = schema_problems(report)
    assert problems, f"mutation {needle!r} was not caught"
    assert any(needle in p for p in problems), problems
    with pytest.raises(SchemaError):
        validate_report(report)


# -- diff discipline ----------------------------------------------------------


def _diff(base, fresh, **kw):
    return diff_reports(base, fresh, **kw)


def test_identical_reports_diff_clean():
    assert _diff(_report(), _report()) == []


@pytest.mark.parametrize("delta", [+7, -7])
def test_cycle_change_fails_both_directions(delta):
    fresh = _report()
    fresh["cells"][0]["cycles"] += delta
    regs = regressions(_diff(_report(), fresh))
    assert len(regs) == 1 and regs[0].kind == "cycles"
    assert regs[0].cell == "table1/binsearch/rhls_dec"
    word = "regressed" if delta > 0 else "improved"
    assert word in regs[0].detail and "refresh the baseline" in regs[0].detail


def test_wall_clock_gates_on_percent_band():
    fresh = _report()
    fresh["cells"][1]["us_warm"] = 120.0 * 1.2       # +20% under a 25% gate
    assert regressions(_diff(_report(), fresh, wall_pct=25.0)) == []
    fresh["cells"][1]["us_warm"] = 120.0 * 1.6       # +60% over it
    regs = regressions(_diff(_report(), fresh, wall_pct=25.0))
    assert [f.kind for f in regs] == ["wall-clock"]
    # improvements are notes, never failures (wall time is noisy)
    fresh["cells"][1]["us_warm"] = 10.0
    findings = _diff(_report(), fresh, wall_pct=25.0)
    assert regressions(findings) == []
    assert [f.kind for f in findings] == ["wall-clock-improved"]


def test_us_cold_is_never_gated():
    fresh = _report()
    fresh["cells"][1]["us_cold"] = 900.0 * 50
    assert _diff(_report(), fresh) == []


def test_removed_cell_fails_new_cell_notes():
    fresh = _report()
    removed = fresh["cells"].pop(0)
    fresh["cells"].append(_cell("table1/spmv/rhls_dec"))
    findings = _diff(_report(), fresh)
    kinds = {f.cell: f.kind for f in findings}
    assert kinds[removed["name"]] == "removed-cell"
    assert kinds["table1/spmv/rhls_dec"] == "new-cell"
    assert [f.cell for f in regressions(findings)] == [removed["name"]]


def test_status_flip_fails_and_short_circuits_timing():
    fresh = _report()
    fresh["cells"][0].update(status="deadlock", cycles=None)
    regs = regressions(_diff(_report(), fresh))
    assert [f.kind for f in regs] == ["status"]   # no trailing cycles noise


def test_integer_derived_exact_floats_informational():
    fresh = _report()
    fresh["cells"][2]["derived"]["channels"] = 3
    fresh["cells"][2]["derived"]["note"] = "y"
    fresh["cells"][2]["derived"]["ratio"] = 1.5
    regs = regressions(_diff(_report(), fresh))
    assert [f.kind for f in regs] == ["derived"]
    assert "channels" in regs[0].detail


def test_coords_drift_is_a_finding():
    fresh = _report()
    fresh["cells"][0]["coords"]["engine"] = "polling"
    regs = regressions(_diff(_report(), fresh))
    assert [f.kind for f in regs] == ["coords"]


def test_mode_mismatch_short_circuits():
    findings = _diff(_report(smoke=True), _report(smoke=False))
    assert [f.kind for f in findings] == ["mode"]
    assert findings[0].fails
    findings = _diff(_report(axis="sim"), _report(axis="kernels"))
    assert [f.kind for f in findings] == ["mode"]


def test_allowlist_downgrades_without_hiding():
    fresh = _report()
    fresh["cells"][0]["cycles"] += 1
    allow = parse_allowlist(
        "# scheduler change lands this PR\nsim/table1/binsearch/*\n")
    findings = _diff(_report(), fresh, allowlist=allow)
    assert regressions(findings) == []            # gate passes...
    assert len(findings) == 1 and findings[0].allowed
    assert "ALLOWED" in findings[0].render()      # ...but the diff still talks
    # the pattern is cell-scoped: other cells still fail
    fresh["cells"][2]["derived"]["channels"] = 9
    assert len(regressions(_diff(_report(), fresh, allowlist=allow))) == 1


# -- registry + report assembly ----------------------------------------------


def test_check_cells_rejects_dupes_and_bad_coords():
    ok = Cell("sim", "a", coords("w", "sim"), run=lambda ctx: CellResult())
    check_cells([ok], "sim")
    with pytest.raises(ValueError, match="duplicate"):
        check_cells([ok, Cell("sim", "a", coords("w", "sim"),
                              run=lambda ctx: CellResult())], "sim")
    with pytest.raises(ValueError, match="axis"):
        check_cells([ok], "kernels")
    with pytest.raises(ValueError, match="kind"):
        coords("w", "simulator")
    with pytest.raises(ValueError, match="tenants"):
        coords("w", "sim", tenants=0)


def test_build_report_validates_and_rounds():
    cell = Cell("sim", "a/b", coords("b", "sim"),
                run=lambda ctx: CellResult(), group="a")
    rep = build_report("sim", [(cell, CellResult(cycles=5,
                                                 us_cold=1.23456,
                                                 us_warm=0.98765))],
                       smoke=True, seed=7)
    row = rep["cells"][0]
    assert (row["us_cold"], row["us_warm"]) == (1.2, 1.0)
    assert rep["meta"]["seed"] == 7
    # the device is recorded as JAX reports it, never left unattributed
    import jax
    dev = jax.devices()[0]
    assert (rep["meta"]["backend"], rep["meta"]["device_kind"],
            rep["meta"]["device_count"]) == (dev.platform, dev.device_kind,
                                             len(jax.devices()))
    with pytest.raises(SchemaError):
        build_report("sim", [(cell, CellResult(us_cold=1.0))],
                     smoke=True, seed=0)


def test_cell_csv_keeps_legacy_shape():
    cell = Cell("sim", "table1/binsearch/rhls_dec", coords("binsearch", "sim"),
                run=lambda ctx: CellResult(), group="table1")
    row = cell_csv(cell, CellResult(cycles=3104, derived={"golden": 3104}))
    assert row == "table1/binsearch/rhls_dec,0,cycles=3104;golden=3104"
    row = cell_csv(cell, CellResult(status="deadlock"))
    assert row.endswith(",0,status=deadlock")


def test_timing_split_measures_cold_then_warm():
    calls = []

    def fn():
        calls.append(1)
        return 0

    from repro.bench import measure
    t = measure(fn, warm_reps=3)
    assert isinstance(t, Timing)
    assert len(calls) == 4                      # 1 cold + 3 warm
    assert t.us_cold >= 0 and t.us_warm >= 0


# -- committed artifacts + enumeration ---------------------------------------


def _baseline(axis):
    path = REPO_ROOT / "benchmarks" / "baseline" / f"BENCH_{axis}.json"
    assert path.exists(), f"missing committed baseline {path.name}"
    return json.loads(path.read_text())


def test_committed_baselines_are_schema_valid_and_self_diff_clean():
    from benchmarks.matrix import AXES
    for axis in AXES:
        report = validate_report(_baseline(axis))
        assert report["axis"] == axis
        assert report["smoke"] is True, "baselines are committed from smoke"
        assert diff_reports(report, copy.deepcopy(report)) == []


def test_matrix_enumerates_without_executing():
    from benchmarks.matrix import AXES, collect
    ctx = BenchContext(smoke=True)
    for axis in AXES:
        cells = collect(axis, ctx)
        assert cells, axis
        check_cells(cells, axis)  # unique names, complete coords


def test_matrix_cells_match_committed_baseline_names():
    """Every declared cell appears in the committed baseline and vice
    versa — a cell added without a baseline refresh (or removed without
    shrinking it) fails here before CI even runs the matrix."""
    from benchmarks.matrix import AXES, collect
    ctx = BenchContext(smoke=True)
    for axis in AXES:
        declared = {c.name for c in collect(axis, ctx)}
        committed = {c["name"] for c in _baseline(axis)["cells"]}
        assert declared == committed, (
            f"axis {axis}: declared-vs-baseline cell mismatch "
            f"(+{sorted(declared - committed)} "
            f"-{sorted(committed - declared)})")


def test_compile_cache_dir_env_then_fixed_checkout_path(monkeypatch,
                                                       tmp_path):
    from repro.launch.compile_cache import ENV_VAR, compile_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert compile_cache_dir() == tmp_path
    monkeypatch.delenv(ENV_VAR)
    assert compile_cache_dir() == REPO_ROOT / ".jax_cache"
