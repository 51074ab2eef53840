"""Device-idle gaps put down to the program's own spans, and the per-layer
metrics that read those spans."""

from __future__ import annotations

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import cells, span_readers, span_trace, trace
from repro import obs

FIXTURES = Path(__file__).resolve().parent / "fixtures"
KERNEL = "rtac_fixpoint_packed"
NS = 1e-9
#: the split of program_spans.pbtxt, worked by hand (ns)
BY_SPAN = {
    "SolverService.step": 1100,  # [500, 650] before service.step, [6050, 7000] after
    "SolverService.step/service.step": 700,
    "SolverService.step/service.admit": 500,
    "SolverService.step/cache.lookup": 300,
    "SolverService.step/slot.install": 900,
    "SolverService.step/driver.round": 200,
    "SolverService.step/round.resolve": 200,
    "SolverService.step/round.wait": 600,
    "SolverService.step/frontier.step": 900,
    "arrival.wait": 2000,
}
UNDER = {
    "service.step": 4300, "service.admit": 1700, "cache.lookup": 1200,
    "slot.install": 900, "driver.round": 1900, "round.resolve": 800,
    "round.wait": 600, "frontier.step": 900,
}


def _write(root: Path, name: str) -> Path:
    """A fixture as a real ``.xplane.pb`` under ``root``, as a run leaves it."""
    path = root / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace((FIXTURES / name).read_text()))
    return path


def _profile(tmp_path: Path, name: str) -> ProfileData:
    return ProfileData.from_file(str(_write(tmp_path, name)))


def _by_prefix(split: dict) -> dict:
    out: dict = {}
    for key, seconds in split.items():
        prefix = key.split("/", 1)[0]
        out[prefix] = out.get(prefix, 0.0) + seconds
    return out


def test_idle_split_by_hand(tmp_path):
    got = span_trace.split(_profile(tmp_path, "program_spans.pbtxt"), obs.SPANS)
    assert got["devices"] == 1 and got["window_s"] == pytest.approx(9500 * NS)
    # eight program spans; JAX's PjitFunction event over [3950, 4050] is not
    # one, so [3950, 4000] stays with frontier.step
    assert got["program_spans"] == 8
    assert got["idle_by_span_s"] == pytest.approx({k: v * NS for k, v in BY_SPAN.items()})
    assert got["idle_under_s"] == pytest.approx({k: v * NS for k, v in UNDER.items()})


def test_the_reduction_is_unchanged_beside_the_split(tmp_path):
    profile = _profile(tmp_path, "program_spans.pbtxt")
    got = trace.reduce(profile, [KERNEL])
    assert got["window_s"] == pytest.approx(9500 * NS)
    assert got["busy_s"] == pytest.approx(2100 * NS)
    assert got["kernel_s"] == {KERNEL: pytest.approx(1000 * NS)}
    assert got["step_other_s"] == pytest.approx(1000 * NS) and got["step_runs"] == 2
    # the gap over [5000, 7000] has its midpoint in the step call
    assert got["idle_s"] == pytest.approx({
        "SolverService.step": 5400 * NS, "arrival.wait": 2000 * NS})
    split = span_trace.split(profile, obs.SPANS)["idle_by_span_s"]
    assert _by_prefix(split) == pytest.approx(got["idle_s"])


@pytest.mark.parametrize("fixture", ["synthetic.pbtxt", "frontier_step_v5e.pbtxt"])
def test_a_trace_without_program_spans_splits_to_its_harness_calls(tmp_path, fixture):
    profile = _profile(tmp_path, fixture)
    got = span_trace.split(profile, obs.SPANS)
    assert got["program_spans"] == 0 and got["idle_under_s"] == {}
    assert got["idle_by_span_s"] == pytest.approx(trace.reduce(profile, [KERNEL])["idle_s"])


def test_segments_nest_and_skip_what_no_span_holds():
    got = span_trace.segments([("b", 2, 4), ("a", 1, 6), ("c", 8, 9)])
    assert got == [(1, 2, ("a",)), (2, 4, ("a", "b")), (4, 6, ("a",)), (8, 9, ("c",))]
    assert span_trace.segments([]) == []


def test_a_trace_with_no_device_is_refused():
    host_only = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        span_trace.split(host_only, obs.SPANS)


def _record(tmp_path, monkeypatch, fixture):
    """A traced run's record beside its trace, where the readers look."""
    root = tmp_path / "traces"
    _write(root / "cell", fixture)
    monkeypatch.setattr(span_readers, "TRACE_ROOT", root)
    profile = ProfileData.from_file(trace.find_xplane(str(root)))
    return {"trace": trace.reduce(profile, [KERNEL]), "kernel": KERNEL,
            "counters": {"driver.rounds": 4}, "spans": {}}


def test_idle_readers_over_a_trace_with_program_spans(tmp_path, monkeypatch):
    rec = _record(tmp_path, monkeypatch, "program_spans.pbtxt")
    assert span_readers.idle_in_admit_pct(rec) == pytest.approx(100 * 1700 / 9500)
    assert span_readers.idle_in_round_pct(rec) == pytest.approx(100 * 1900 / 9500)


def test_idle_readers_read_nothing_without_program_spans(tmp_path, monkeypatch):
    rec = _record(tmp_path, monkeypatch, "synthetic.pbtxt")
    assert span_readers.idle_in_admit_pct(rec) is None
    assert span_readers.idle_in_round_pct(rec) is None


def test_idle_readers_read_nothing_from_another_runs_trace(tmp_path, monkeypatch):
    rec = _record(tmp_path, monkeypatch, "program_spans.pbtxt")
    rec["trace"]["idle_s"] = {"SolverService.step": 1.0}
    assert span_readers.idle_in_round_pct(rec) is None


def test_idle_readers_read_nothing_where_the_program_declares_no_spans(tmp_path, monkeypatch):
    rec = _record(tmp_path, monkeypatch, "program_spans.pbtxt")
    monkeypatch.delattr(obs, "SPANS")
    assert span_readers.idle_in_admit_pct(rec) is None


def test_idle_readers_read_nothing_untraced_or_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(span_readers, "TRACE_ROOT", tmp_path / "none")
    assert span_readers.idle_in_round_pct({"counters": {}, "spans": {}}) is None
    rec = {"trace": {"idle_s": {}}, "counters": {}, "spans": {}}
    assert span_readers.idle_in_round_pct(rec) is None


def test_span_readers():
    rec = {"counters": {"driver.rounds": 4},
           "spans": {"service.admit": [0.002, 0.006], "slot.install": [0.001, 0.003],
                     "round.wait": [0.004] * 4}}
    assert span_readers.admit_ms_per_round(rec) == pytest.approx(2.0)
    assert span_readers.install_ms(rec) == pytest.approx(2.0)
    assert span_readers.resolve_wait_ms_per_round(rec) == pytest.approx(4.0)


@pytest.mark.parametrize("reader", [
    span_readers.admit_ms_per_round, span_readers.install_ms,
    span_readers.resolve_wait_ms_per_round,
])
def test_span_readers_read_nothing_without_their_spans(reader):
    assert reader({"counters": {"driver.rounds": 4}, "spans": {}}) is None
    # spans but no round: nothing to divide by
    if reader is not span_readers.install_ms:
        spans = {"service.admit": [1.0], "round.wait": [1.0]}
        assert reader({"counters": {}, "spans": spans}) is None


@pytest.mark.parametrize("workload,names", [
    ("frb50-poisson", {"admit_ms_per_round.service", "install_ms.service",
                       "resolve_wait_ms_per_round.service", "idle_in_admit_pct.service",
                       "idle_in_round_pct.service"}),
    ("frb50-zipf", {"admit_ms_per_round.service", "install_ms.service",
                    "resolve_wait_ms_per_round.service", "idle_in_admit_pct.service",
                    "idle_in_round_pct.service"}),
    ("frb100-batch24", {"resolve_wait_ms_per_round.batch", "idle_in_round_pct.batch"}),
])
def test_cells_read_the_span_metrics(workload, names):
    assert names <= set(cells.load(workload).readers)
