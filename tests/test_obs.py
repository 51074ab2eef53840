"""Observability layer (DESIGN.md §10): tracer integrity, registry schema,
Perfetto export, and the no-semantic-footprint guarantee.

The load-bearing claims:

- nested spans close in order with correct parent links, and the ring stays
  bounded under arbitrarily long runs;
- tracing changes NOTHING: solver verdicts and per-instance stats are
  bit-identical with tracing off, on ("async"), and on with fenced timing,
  and the fenced mode stays clean under ``jax.transfer_guard("disallow")``;
- the exported timeline is valid Chrome trace-event JSON (what
  ui.perfetto.dev loads), and ``driver.round`` spans decompose into child
  phases covering ≥ 90% of round wall-clock on a real service run;
- `ServiceMetrics` snapshots are NaN-free on empty windows and at
  ``window=1``, via the one shared percentile/mean implementation.
"""

import glob
import json
import math
import re
from pathlib import Path

import jax
import pytest

from repro import obs
from repro.core import mac_solve, solve_many
from repro.problems import generate, generate_batch
from repro.service import FastForwardClock, SolverService, poisson_trace, replay
from repro.service.buckets import speculative_budget
from repro.service.metrics import ServiceMetrics

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends tracing-off with an empty registry, so the
    suite leaves no footprint on other test modules."""
    obs.disable()
    obs.REGISTRY.reset()
    yield
    obs.disable()
    obs.REGISTRY.reset()


# --- tracer core -------------------------------------------------------------


def test_nested_spans_parent_links_and_ordering():
    tracer = obs.enable()
    with obs.span("outer", cat="t") as s_out:
        with obs.span("inner", cat="t") as s_in:
            with obs.span("leaf", cat="t") as s_leaf:
                pass
        with obs.span("inner2", cat="t") as s_in2:
            pass
    assert tracer.open_spans == 0
    assert s_in.parent == s_out.sid and s_in2.parent == s_out.sid
    assert s_leaf.parent == s_in.sid
    assert s_out.parent == 0
    # children close before parents land in the ring, intervals nest
    spans = tracer.snapshot_spans()
    assert [s["name"] for s in spans] == ["leaf", "inner", "inner2", "outer"]
    by_sid = {s["sid"]: s for s in spans}
    for s in spans:
        p = by_sid.get(s["parent"])
        if p is not None:
            assert p["t0"] <= s["t0"]
            assert s["t0"] + s["dur"] <= p["t0"] + p["dur"] + 1e-9


def test_span_args_attach_at_enter_and_after():
    tracer = obs.enable()
    with obs.span("work", rows=7) as s:
        s.args["hit"] = True
    rec = tracer.snapshot_spans()[0]
    assert rec["args"] == {"rows": 7, "hit": True}


def test_ring_bounded_under_long_runs():
    tracer = obs.enable(capacity=32)
    for i in range(100):
        with obs.span("tick", i=i):
            pass
    assert len(tracer.spans) == 32
    assert tracer.dropped == 100 - 32
    # oldest rolled off: the survivors are the most recent 32
    assert [s["args"]["i"] for s in tracer.snapshot_spans()] == list(range(68, 100))


def test_mismatched_exit_force_closes_instead_of_corrupting():
    tracer = obs.enable()
    outer = tracer.begin("outer")
    tracer.begin("orphan")  # never explicitly closed
    tracer.end(outer)
    assert tracer.open_spans == 0
    assert tracer.force_closed == 1
    names = [s["name"] for s in tracer.snapshot_spans()]
    assert names == ["orphan", "outer"]


def test_disabled_path_is_inert():
    assert not obs.enabled()
    ctx = obs.span("anything", rows=3)
    ctx2 = obs.span("else")
    assert ctx is ctx2  # one shared null context manager — no allocation
    with ctx as s:
        assert s is None
    assert obs.now() == 0.0
    obs.record_complete("late", 0.0, 1.0)  # no tracer: silently dropped
    obs.fence(object())  # no jax import, no-op on arbitrary values


def test_disable_returns_tracer_with_spans_intact():
    obs.enable()
    with obs.span("kept"):
        pass
    tracer = obs.disable()
    assert not obs.enabled()
    assert [s["name"] for s in tracer.snapshot_spans()] == ["kept"]


def test_enable_from_env():
    assert not obs.enable_from_env({})
    assert not obs.enable_from_env({"REPRO_TRACE": "0"})
    assert not obs.enable_from_env({"REPRO_TRACE": "false"})
    assert not obs.enable_from_env({"REPRO_TRACE": "off"})
    assert not obs.enabled()
    assert obs.enable_from_env(
        {"REPRO_TRACE": "1", "REPRO_TRACE_TIMING": "fenced", "REPRO_TRACE_RING": "64"}
    )
    tracer = obs.get_tracer()
    assert tracer.timing == "fenced" and tracer.capacity == 64


def test_tracer_rejects_bad_config():
    with pytest.raises(ValueError):
        obs.Tracer(timing="blocking")
    with pytest.raises(ValueError):
        obs.Tracer(capacity=0)


# --- one clock with the profiler ---------------------------------------------


def _host_events(log_dir, names):
    """``{name: [(start_ns, end_ns, line)]}`` of a profile's host planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, line.name))
    return out


def test_spans_land_in_a_profiler_trace_with_their_nesting(tmp_path):
    names = {"driver.round", "round.resolve", "round.wait", "frontier.step"}
    obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("driver.round"):
            with obs.span("round.resolve"):
                with obs.span("round.wait"):
                    jax.device_get(jax.numpy.arange(4) + 1)
            with obs.span("frontier.step"):
                pass
    tracer = obs.disable()
    got = _host_events(tmp_path, names)
    assert {k: len(v) for k, v in got.items()} == dict.fromkeys(names, 1)
    (rnd,), (res,), (wait,), (step,) = (got[n] for n in
                                        ("driver.round", "round.resolve", "round.wait",
                                         "frontier.step"))
    assert len({rnd[2], res[2], wait[2], step[2]}) == 1  # one thread's line
    assert rnd[0] <= res[0] <= wait[0] and wait[1] <= res[1] <= step[0] <= step[1] <= rnd[1]
    # the ring holds the same spans
    assert {s["name"] for s in tracer.snapshot_spans()} == names


def test_no_trace_annotation_is_made_while_tracing_is_off(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    with obs.span("driver.round"):
        pass
    solve_many(generate_batch("model_rb", 2, n=8, hardness=1.0, seed=4), engine="einsum")
    assert made == []
    # on, the tracer resolves the annotation at enable() and opens one a span
    obs.enable()
    with obs.span("driver.round"), obs.span("round.wait"):
        pass
    assert made == ["driver.round", "round.wait"]


def test_a_bare_tracer_opens_no_annotation(monkeypatch):
    """The recording core (`Tracer.begin` / `end`) keeps to its ring; only a
    `span()` of the tracer `enable()` installed opens the annotation."""
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", lambda name: made.append(name))
    tracer = obs.Tracer()
    tracer.end(tracer.begin("driver.round"))
    assert made == [] and [s["name"] for s in tracer.snapshot_spans()] == ["driver.round"]
    obs.enable()
    assert obs.tracing._ANNOTATION is jax.profiler.TraceAnnotation


def test_every_span_the_program_opens_is_declared():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'obs\.span\(\s*"([^"]+)"', path.read_text()))
    assert opened == obs.SPANS


def test_round_wait_nests_in_the_resolve_of_a_device_frontier_round():
    tracer = obs.enable()
    solve_many(generate_batch("model_rb", 3, n=10, hardness=1.0, seed=2), engine="einsum")
    spans = tracer.snapshot_spans()
    by_sid = {s["sid"]: s for s in spans}
    waits = [s for s in spans if s["name"] == "round.wait"]
    assert waits and all(by_sid[s["parent"]]["name"] == "round.resolve" for s in waits)


def test_solve_many_names_its_preparation():
    tracer = obs.enable()
    solve_many(generate_batch("model_rb", 3, n=10, hardness=1.0, seed=2), engine="einsum")
    spans = tracer.snapshot_spans()
    (prep,) = [s for s in spans if s["name"] == "many.prepare"]
    rounds = [s for s in spans if s["name"] == "driver.round"]
    assert prep["parent"] == 0 and prep["args"] == {"n": 3}
    assert rounds and all(s["t0"] >= prep["t0"] + prep["dur"] for s in rounds)


def test_rounds_publish_through_the_driver_alone():
    """`ServiceMetrics` still counts rounds for its snapshot, but leaves the
    registry to the driver's own ``driver.rounds`` / ``driver.rows``."""
    m = ServiceMetrics()
    m.record_round(rows=4, searches=2, seconds=0.01)
    assert m.snapshot()["rows_dispatched"] == 4 and m.n_rounds == 1
    snap = obs.snapshot()
    assert not [k for part in ("counters", "histograms") for k in snap[part]
                if k.startswith("service.")]


# --- no semantic footprint: verdict parity across tracing modes --------------


def test_mac_solve_verdicts_identical_across_tracing_modes():
    """Tracing off / async / fenced: bit-identical solutions and stats."""
    csps = [
        generate("model_rb", n=10, hardness=1.0, seed=3),
        generate("coloring_random", n=12, edge_prob=0.3, k=3, seed=1),
    ]
    ref = [mac_solve(c, engine="einsum") for c in csps]
    for timing in ("async", "fenced"):
        obs.enable(timing=timing)
        for c, (ref_sol, ref_st) in zip(csps, ref):
            sol, st = mac_solve(c, engine="einsum")
            assert sol == ref_sol
            assert st.n_assignments == ref_st.n_assignments
            assert st.n_backtracks == ref_st.n_backtracks
            assert st.recurrences == ref_st.recurrences
        obs.disable()


def test_fenced_tracing_stays_clean_under_transfer_guard():
    """`fence()` uses block_until_ready — no transfer — so the device-resident
    frontier's ``disallow`` audit passes with fenced tracing on, and the
    verdicts match the untraced run."""
    csps = generate_batch("model_rb", 4, n=10, hardness=1.0, seed=5)
    ref_sols, ref_stats = solve_many(csps, engine="einsum")
    obs.enable(timing="fenced")
    with jax.transfer_guard("disallow"):
        sols, stats = solve_many(csps, engine="einsum")
    assert sols == ref_sols
    assert [s.recurrences for s in stats] == [s.recurrences for s in ref_stats]
    tracer = obs.disable()
    names = {s["name"] for s in tracer.snapshot_spans()}
    assert {"driver.round", "frontier.step", "kernel.launch"} <= names


def test_driver_counters_published_by_solve_many():
    csps = generate_batch("model_rb", 3, n=10, hardness=1.0, seed=2)
    solve_many(csps, engine="einsum")
    snap = obs.snapshot()
    assert snap["counters"]["driver.rounds"] > 0
    assert snap["counters"]["driver.launches"] > 0
    assert snap["counters"]["many.solves"] == 3
    hist = snap["histograms"]["many.rounds_per_instance"]
    assert hist["count"] == 3 and hist["max"] >= hist["p50"] > 0


# --- registry ----------------------------------------------------------------


def test_registry_snapshot_schema_and_reduction():
    obs.counter_add("a.count")
    obs.counter_add("a.count", 4)
    obs.gauge_set("b.level", 7.5)
    for v in range(1, 11):
        obs.observe("c.lat", float(v))
    snap = obs.snapshot()
    assert snap["schema"] == "repro-obs/v1"
    assert snap["counters"] == {"a.count": 5}
    assert snap["gauges"] == {"b.level": 7.5}
    h = snap["histograms"]["c.lat"]
    assert h["count"] == 10 and h["min"] == 1.0 and h["max"] == 10.0
    assert h["p50"] == pytest.approx(5.5)
    obs.REGISTRY.reset()
    assert obs.snapshot()["counters"] == {}


def test_shared_percentile_helpers_never_nan():
    assert obs.percentile([], 95) == 0.0
    assert obs.mean([]) == 0.0
    s = obs.summarize([])
    assert s["count"] == 0
    assert all(not math.isnan(float(v)) for v in s.values())
    assert obs.percentile([3.0], 99) == 3.0  # window=1 degenerates finitely


def test_speculative_budget_publishes_grant_deny():
    # queue at limit: denied
    assert speculative_budget(2, 2, queue_depth=9, spare_rows=64, queue_limit=9) == (0, 0)
    # slack: granted (possibly clamped)
    split, port = speculative_budget(2, 2, queue_depth=0, spare_rows=64, queue_limit=9)
    assert (split, port) == (2, 2)
    snap = obs.snapshot()["counters"]
    assert snap["speculation.denied"] == 1
    assert snap["speculation.split_granted"] == 2
    assert snap["speculation.portfolio_granted"] == 2


# --- ServiceMetrics: NaN-free empty / window=1 snapshots ---------------------


def test_metrics_empty_snapshot_is_exact_zeros():
    snap = ServiceMetrics().snapshot()
    for key, val in snap.items():
        assert not math.isnan(float(val)), key
    assert snap["p95_ms"] == 0.0 and snap["p99_ms"] == 0.0
    assert snap["throughput_rps"] == 0.0
    assert snap["mean_launches_per_round"] == 0.0
    assert snap["median_rows_per_request"] == 0.0


def test_metrics_window_one_stays_finite():
    m = ServiceMetrics(window=1)
    m.record_submit(0.0)
    m.record_finish(1.0, 0.25, "done")
    m.record_finish(2.0, 0.75, "done")  # window=1: only the last sample held
    m.record_round(rows=4, searches=2, seconds=0.01, launches=3)
    m.record_queue_depth(5)
    m.record_request_rows(2, members=1, cancelled=0)
    snap = m.snapshot()
    for key, val in snap.items():
        assert not math.isnan(float(val)), key
    assert snap["p50_ms"] == snap["p99_ms"] == pytest.approx(750.0)
    assert snap["mean_launches_per_round"] == 3.0


# --- export: Chrome trace-event schema + coverage ----------------------------


def _valid_chrome_trace(doc):
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert events[0] == {
        "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
        "args": {"name": "repro"},
    }
    named_tids = set()
    for ev in events:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M" and ev["name"] == "thread_name":
            named_tids.add(ev["tid"])
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
            assert isinstance(ev["name"], str) and isinstance(ev["args"], dict)
            assert ev["tid"] in named_tids  # every track is labeled
    return events


def test_chrome_trace_schema_from_synthetic_spans():
    tracer = obs.enable()
    with obs.span("driver.round", cat="driver"):
        with obs.span("kernel.launch", cat="kernel", rows=4):
            pass
    t0 = tracer.now()
    obs.record_complete("service.request", t0, t0 + 0.01,
                        track="requests", id=0, status="done")
    doc = obs.chrome_trace(tracer.snapshot_spans(), origin=tracer.origin)
    events = _valid_chrome_trace(doc)
    body = [ev for ev in events if ev["ph"] == "X"]
    assert {ev["name"] for ev in body} == {
        "driver.round", "kernel.launch", "service.request"
    }
    # round-trips through JSON untouched
    assert json.loads(json.dumps(doc)) == doc


def test_child_coverage_degenerate_cases():
    assert obs.child_coverage([], "driver.round") == 1.0
    spans = [
        {"sid": 1, "parent": 0, "name": "driver.round", "t0": 0.0, "dur": 1.0},
        {"sid": 2, "parent": 1, "name": "frontier.step", "t0": 0.0, "dur": 0.95},
    ]
    assert obs.child_coverage(spans, "driver.round") == pytest.approx(0.95)


# --- the acceptance run: traced service replay -------------------------------


def _traced_service_run(timing="async"):
    obs.enable(timing=timing)
    events = poisson_trace(["model_rb"], rate=6.0, duration=1.0, seed=0)
    clock = FastForwardClock()
    svc = SolverService(engine="einsum", clock=clock)
    requests = replay(svc, events, clock)
    return svc, requests, obs.get_tracer()


def test_traced_service_round_coverage_and_request_spans():
    """ISSUE 8 acceptance: driver.round child spans cover ≥ 90% of round
    wall-clock, request-lifetime spans are filed per retired request, and the
    registry carries the same solve counts the service reports."""
    svc, requests, tracer = _traced_service_run()
    spans = tracer.snapshot_spans()
    assert obs.child_coverage(spans, "driver.round") >= 0.9
    req_spans = [s for s in spans if s["name"] == "service.request"]
    assert len(req_spans) == len(requests)
    assert {s["args"]["status"] for s in req_spans} <= {"done", "timed_out", "cancelled"}
    snap = obs.snapshot()
    assert snap["counters"]["service.completed"] == svc.metrics.n_completed
    assert snap["counters"]["cache.misses"] >= 1
    _valid_chrome_trace(obs.chrome_trace(spans, origin=tracer.origin))


def test_traced_service_verdicts_match_untraced():
    svc0, ref, _tracer0 = _traced_service_run()
    obs.disable()
    obs.REGISTRY.reset()
    events = poisson_trace(["model_rb"], rate=6.0, duration=1.0, seed=0)
    clock = FastForwardClock()
    svc = SolverService(engine="einsum", clock=clock)
    untraced = replay(svc, events, clock)
    assert [r.solution for r in ref] == [r.solution for r in untraced]
    assert [r.stats.n_assignments for r in ref] == [
        r.stats.n_assignments for r in untraced
    ]


# --- run dump + CLI ----------------------------------------------------------


def test_run_dump_roundtrip_and_cli(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main

    _svc, _requests, tracer = _traced_service_run()
    run_path = tmp_path / "run.json"
    payload = obs.dump_run(run_path, tracer=tracer)
    assert payload["schema"] == "repro-obs/v1"
    assert payload["snapshot"]["schema"] == "repro-obs/v1"
    assert payload["tracer"]["timing"] == "async"
    assert len(payload["spans"]) > 0

    assert obs_main(["summarize", str(run_path)]) == 0
    out = capsys.readouterr().out
    assert "driver.round" in out and "child coverage" in out
    assert "service.completed" in out

    trace_path = tmp_path / "out.perfetto.json"
    assert obs_main(["export", str(run_path), "-o", str(trace_path)]) == 0
    doc = json.loads(trace_path.read_text())
    _valid_chrome_trace(doc)

    # write_trace directly from the live tracer agrees event-for-event
    direct = tmp_path / "direct.json"
    obs.write_trace(direct, tracer)
    assert json.loads(direct.read_text())["traceEvents"] == doc["traceEvents"]


def test_load_run_rejects_foreign_schema(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "something/v9"}))
    with pytest.raises(ValueError):
        obs.load_run(bad)
    with pytest.raises(RuntimeError):
        obs.write_trace(tmp_path / "x.json", None)  # tracing off


def test_run_payload_with_tracing_off():
    obs.counter_add("solo.count")
    payload = obs.run_payload()
    assert payload["spans"] == [] and payload["tracer"] is None
    assert payload["snapshot"]["counters"]["solo.count"] == 1
