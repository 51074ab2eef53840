"""Sweep harness tests: spec round-trips, grid determinism, runner resume,
and the report golden path — all CPU-tiny and engine-light."""

import json

import pytest

from repro.sweeps import (
    SCHEMA,
    SweepSpec,
    load_cells,
    load_spec,
    loads_toml,
    run_spec,
    sweep_dir,
)
from repro.sweeps.spec import available_specs


def _tiny_spec(**overrides):
    doc = {
        "schema": SCHEMA,
        "name": "t_tiny",
        "title": "tiny",
        "mode": "solve_many",
        "seed": 3,
        "replicates": 2,
        "problem": {
            "family": "random_binary",
            "knobs": {"n": [6, 8], "tightness": [0.2, 0.3], "d": 4,
                      "density": 0.5},
        },
        "solver": {"engine": "einsum"},
    }
    doc.update(overrides)
    return SweepSpec.from_doc(doc)


# --------------------------------------------------------------------------
# TOML subset parser + round-trip
# --------------------------------------------------------------------------


def test_toml_round_trip_both_parsers():
    """dumps_toml output parses back through tomllib to the same spec, and
    emitting the parsed document again is a fixed point."""
    spec = _tiny_spec()
    text = spec.to_toml()
    doc = loads_toml(text)
    assert SweepSpec.from_doc(doc) == spec
    assert SweepSpec.from_doc(doc).to_toml() == text


def test_toml_subset_scalars_arrays_comments():
    doc = loads_toml(
        '\n'.join([
            '# leading comment',
            'name = "x"  # trailing comment',
            'count = 3',
            'ratio = 0.5',
            'flag = true',
            'items = [1, 2, 3]',
            'mixed = ["a", "b"]',
            '',
            '[table]',
            'k = "v"',
            '[table.sub]',
            'j = 2',
        ])
    )
    assert doc == {
        "name": "x", "count": 3, "ratio": 0.5, "flag": True,
        "items": [1, 2, 3], "mixed": ["a", "b"],
        "table": {"k": "v", "sub": {"j": 2}},
    }


def test_toml_subset_rejects_garbage():
    for bad in ("just words", "[unclosed", 'k = "no end', "k ="):
        with pytest.raises(ValueError):
            loads_toml(bad)


def test_committed_specs_load_and_expand():
    names = available_specs()
    assert {"model_rb_phase", "recurrence_density", "service_capacity",
            "cache_pool", "smoke"} <= set(names)
    for name in names:
        spec = load_spec(name)
        cells = spec.cells()
        assert cells, name
        # to_toml -> from_toml is identity for every committed spec
        assert SweepSpec.from_toml(spec.to_toml()) == spec


# --------------------------------------------------------------------------
# deterministic grid expansion
# --------------------------------------------------------------------------


def test_grid_is_deterministic_and_sorted():
    """Byte-identical cell list on re-expansion, independent of knob
    declaration order in the file."""
    a = _tiny_spec()
    ids = [c.cell_id for c in a.cells()]
    assert ids == [c.cell_id for c in a.cells()]
    assert len(set(ids)) == len(ids) == 4
    # same knobs, reversed declaration order -> same grid
    b = _tiny_spec(problem={
        "family": "random_binary",
        "knobs": {"density": 0.5, "d": 4, "tightness": [0.2, 0.3],
                  "n": [6, 8]},
    })
    assert [c.cell_id for c in b.cells()] == ids


def test_workload_seed_ignores_engine():
    spec = SweepSpec.from_doc({
        "schema": SCHEMA, "name": "t_seed", "mode": "assignments",
        "problem": {"family": "random_binary", "knobs": {"n": [6]}},
        "solver": {"engine": ["einsum", "ac3"], "n_assignments": 2},
    })
    cells = spec.cells()
    assert len(cells) == 2
    assert spec.workload_seed(cells[0]) == spec.workload_seed(cells[1])


def test_spec_validation_errors():
    with pytest.raises(ValueError):
        _tiny_spec(mode="nope")
    with pytest.raises(TypeError):  # unknown generator knob
        _tiny_spec(problem={"family": "random_binary",
                            "knobs": {"bogus": [1, 2]}})
    with pytest.raises(ValueError):  # duplicate knob across tables
        _tiny_spec(solver={"engine": "einsum", "n": 4})
    with pytest.raises(ValueError):  # service mode needs rate
        SweepSpec.from_doc({
            "schema": SCHEMA, "name": "t_svc", "mode": "service",
            "service": {"families": ["model_rb"], "duration": 1.0},
        })


# --------------------------------------------------------------------------
# resumable runner
# --------------------------------------------------------------------------


def test_runner_resume_after_interrupt(tmp_path):
    """Interrupting a sweep (simulated by truncating cells.jsonl) and
    re-running executes only the missing cells — no duplicates."""
    spec = _tiny_spec()
    d = run_spec(spec, out_root=tmp_path, progress=None)
    cells_path = d / "cells.jsonl"
    lines = cells_path.read_text().splitlines(keepends=True)
    assert len(lines) == 1 + 4  # header + one record per cell
    full = load_cells(cells_path)

    # interrupt: keep header + 2 records + a torn partial third line
    cells_path.write_text("".join(lines[:3]) + lines[3][: len(lines[3]) // 2])
    assert len(load_cells(cells_path)) == 2  # torn tail tolerated

    run_spec(spec, out_root=tmp_path, progress=None)
    resumed = load_cells(cells_path)
    ids = [r["cell"] for r in resumed]
    assert sorted(ids) == sorted(r["cell"] for r in full)
    assert len(set(ids)) == len(ids) == 4
    # identical params+seed produce identical deterministic metrics
    by_id_full = {r["cell"]: r for r in full}
    for r in resumed:
        assert r["seed"] == by_id_full[r["cell"]]["seed"]
        assert r["metrics"]["solve_rate"] == \
            by_id_full[r["cell"]]["metrics"]["solve_rate"]


def test_runner_refuses_changed_spec(tmp_path):
    spec = _tiny_spec()
    run_spec(spec, out_root=tmp_path, progress=None)
    changed = _tiny_spec(seed=99)
    with pytest.raises(RuntimeError, match="different spec"):
        run_spec(changed, out_root=tmp_path, progress=None)
    # fresh=True wipes and reruns the new grid
    d = run_spec(changed, out_root=tmp_path, fresh=True, progress=None)
    assert all(r["seed"] != s for r, s in zip(
        load_cells(d / "cells.jsonl"),
        [spec.workload_seed(c) for c in spec.cells()],
    ))


def test_record_schema_and_obs_delta(tmp_path):
    spec = _tiny_spec()
    d = run_spec(spec, out_root=tmp_path, progress=None)
    for rec in load_cells(d / "cells.jsonl"):
        assert rec["schema"] == SCHEMA
        assert set(rec) >= {"cell", "params", "seed", "metrics", "obs",
                            "cell_seconds"}
        m = rec["metrics"]
        assert 0.0 <= m["solve_rate"] <= 1.0
        assert m["n_instances"] == spec.replicates
        # per-cell obs delta scoped that cell's driver work
        assert rec["obs"]["counters"].get("driver.rounds", 0) > 0
    assert sweep_dir(spec, tmp_path) == d
    assert (d / "spec.toml").exists()


# --------------------------------------------------------------------------
# report: figures + golden section from fixture artifacts
# --------------------------------------------------------------------------


def _fixture_records(spec, metric_rows):
    """Minimal cell records for report tests."""
    recs = []
    for i, (params, metrics) in enumerate(metric_rows):
        recs.append({
            "schema": SCHEMA, "sweep": spec.name, "cell": str(i),
            "params": params, "seed": i, "replicates": spec.replicates,
            "cell_seconds": 0.1, "metrics": metrics, "obs": {},
        })
    return recs


def test_report_section_golden_and_deterministic():
    """A claim section built from fixture records is stable across calls and
    carries figure, verdict, and spec — the byte-stability the CI drift gate
    (`check_report`) relies on."""
    from repro.sweeps.report import CLAIMS, claim_section

    claim = next(c for c in CLAIMS if c.key == "phase-transition")
    spec = load_spec(claim.sweep)
    rows = []
    for n in (10, 14):
        for h, sr in ((0.6, 1.0), (1.0, 0.5), (1.4, 0.0)):
            rows.append((
                {"n": n, "hardness": h, "engine": "einsum"},
                {"solve_rate": sr, "median_assignments": 4.0,
                 "median_latency_ms": 1.0},
            ))
    records = _fixture_records(spec, rows)
    sec1 = claim_section(claim, spec, records, 3, "figs")
    sec2 = claim_section(claim, spec, records, 3, "figs")
    assert sec1 == sec2  # byte-identical regeneration
    assert "**Verdict: PASS**" in sec1
    assert "figs/model_rb_solve_rate.svg" in sec1
    assert "```toml" in sec1 and claim.sweep in sec1
    # figures are pure functions of the records
    fig = claim.figures[0]
    assert fig.build(records, spec) == fig.build(records, spec)
    svg = fig.build(records, spec)
    assert svg.startswith("<svg") and svg.rstrip().endswith("</svg>")


def test_report_verdict_deviates_on_bad_data():
    from repro.sweeps.report import CLAIMS, claim_section

    claim = next(c for c in CLAIMS if c.key == "phase-transition")
    spec = load_spec(claim.sweep)
    rows = [(
        {"n": 10, "hardness": 1.4, "engine": "einsum"},
        {"solve_rate": 0.9, "median_assignments": 1.0,
         "median_latency_ms": 1.0},  # solved deep in the UNSAT region
    )]
    sec = claim_section(claim, spec, _fixture_records(spec, rows), 3, "figs")
    assert "**Verdict: DEVIATES**" in sec


def test_committed_results_pass_drift_gate():
    """The committed results/ + RESULTS.md regenerate byte-identically —
    exactly what CI's sweep-smoke leg asserts."""
    from repro.sweeps.report import check_report
    from repro.sweeps.runner import DEFAULT_OUT_ROOT

    if not DEFAULT_OUT_ROOT.exists():
        pytest.skip("no committed results/ (fresh checkout before first run)")
    assert check_report() == []


def test_line_chart_guardrails():
    from repro.sweeps import Series, line_chart

    with pytest.raises(ValueError, match="at least one"):
        line_chart([], title="t", xlabel="x", ylabel="y")
    too_many = [Series(str(i), [0, 1], [0, i]) for i in range(5)]
    with pytest.raises(ValueError, match="palette"):
        line_chart(too_many, title="t", xlabel="x", ylabel="y")
    svg = line_chart(
        [Series("a", [1, 2, 4], [1.0, 10.0, 100.0]),
         Series("b", [1, 2, 4], [2.0, 3.0, 4.0])],
        title="t", xlabel="x", ylabel="y", yscale="log",
        refline=(50.0, "SLO"),
    )
    assert svg == line_chart(  # deterministic output
        [Series("a", [1, 2, 4], [1.0, 10.0, 100.0]),
         Series("b", [1, 2, 4], [2.0, 3.0, 4.0])],
        title="t", xlabel="x", ylabel="y", yscale="log",
        refline=(50.0, "SLO"),
    )
    assert "SLO" in svg and "#d03b3b" in svg  # labelled threshold line
    assert svg.count("<circle") == 6  # surface-ringed markers per point


def test_registry_scope_isolates_cells():
    from repro import obs

    obs.counter_add("t_scope.outer", 2.0)
    with obs.REGISTRY.scope() as scope:
        obs.counter_add("t_scope.inner", 3.0)
        obs.observe("t_scope.h", 1.0)
        obs.observe("t_scope.h", 5.0)
    delta = scope.delta()
    assert delta["counters"].get("t_scope.inner") == 3.0
    assert "t_scope.outer" not in delta["counters"]
    assert delta["histograms"]["t_scope.h"]["count"] == 2
    # the scope never mutates the registry itself
    assert json.dumps(obs.snapshot())  # still a valid full snapshot
