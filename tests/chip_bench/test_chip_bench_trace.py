"""The trace reduction: device busy and idle, kernel time, the frontier
step's other ops, and idle gaps put down to the harness call around them."""

from __future__ import annotations

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import readers, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"
KERNEL = "rtac_fixpoint_packed"


def _from_file(tmp_path: Path, name: str) -> ProfileData:
    """Through a real ``.xplane.pb`` file, as a run reads its trace."""
    text = (FIXTURES / name).read_text()
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return ProfileData.from_file(trace.find_xplane(str(tmp_path)))


def test_synthetic_trace_by_hand(tmp_path):
    got = trace.reduce(_from_file(tmp_path, "synthetic.pbtxt"), [KERNEL])
    ns = 1e-9
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(9500 * ns)  # the window annotation
    # ops [1000, 5000] and [7000, 8000]; the op at 20000 lies past the window
    assert got["busy_s"] == pytest.approx(5000 * ns)
    assert got["kernel_s"] == {KERNEL: pytest.approx(2500 * ns)}
    # fusion.1 and copy.65 ran inside the frontier step; pad.1 in another program
    assert got["step_other_s"] == pytest.approx(1500 * ns) and got["step_runs"] == 1
    assert got["ops_s"] == pytest.approx({
        "jit__frontier_step/fusion.1": 1000 * ns,
        "jit__frontier_step/rtac_fixpoint_packed.1": 2500 * ns,
        "jit__frontier_step/copy.65": 500 * ns,
        "jit__pad/pad.1": 1000 * ns,
    })
    # gaps [500, 1000] and [5000, 7000] fall in the step call, [8000, 10000]
    # in the wait for the next arrival; the program's own host events are not
    # the harness's and name nothing
    assert got["idle_s"] == pytest.approx({
        "SolverService.step": 2500 * ns, "arrival.wait": 2000 * ns})
    assert trace.top(got["idle_s"], 1) == [["SolverService.step", pytest.approx(2500 * ns)]]


def test_readers_over_a_reduced_trace(tmp_path):
    rec = {
        "trace": trace.reduce(_from_file(tmp_path, "synthetic.pbtxt"), [KERNEL]),
        "kernel": KERNEL, "kernel_row_bytes": 672_008, "hbm_bytes_per_s": 819e9,
        "counters": {"driver.rounds": 1, "driver.rows": 3}, "spans": {},
    }
    assert readers.device_idle_pct(rec) == pytest.approx(100 * (1 - 5000 / 9500))
    assert readers.kernel_ms_per_round(rec) == pytest.approx(2500e-6)
    assert readers.step_other_ms_per_round(rec) == pytest.approx(1500e-6)
    # 3 rows of 672,008 B at 819 GB/s take 2.46 us; the kernel took 2.5 us
    assert readers.kernel_roofline_pct(rec) == pytest.approx(100 * 3 * 672_008 / 819e9 / 2500e-9)


def test_a_trace_without_the_kernel_reads_nothing(tmp_path):
    rec = {"trace": trace.reduce(_from_file(tmp_path, "synthetic.pbtxt"), ["rtac_other"]),
           "kernel": "rtac_other", "kernel_row_bytes": 1, "hbm_bytes_per_s": 1.0,
           "counters": {"driver.rounds": 1, "driver.rows": 3}, "spans": {}}
    assert readers.kernel_ms_per_round(rec) is None
    assert readers.kernel_roofline_pct(rec) is None


def test_a_trace_with_no_device_is_refused():
    host_only = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace.reduce(host_only, [KERNEL])


def test_recorded_frontier_step(tmp_path):
    """One frontier-step run recorded on a TPU v5 lite: the kernel's time is
    its one event's, the step's other time is the sum of its other ops, and
    every idle nanosecond of the window fell inside the ``solve_many`` call."""
    profile = _from_file(tmp_path, "frontier_step_v5e.pbtxt")
    got = trace.reduce(profile, [KERNEL])
    device = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = list(next(line for line in device.lines if line.name == "XLA Ops").events)
    (module,) = next(line for line in device.lines if line.name == "XLA Modules").events
    kernel = [e.duration_ns for e in ops if "rtac_fixpoint_packed" in e.name]
    lo, hi = module.start_ns, module.start_ns + module.duration_ns
    others = [e.duration_ns for e in ops
              if "rtac_fixpoint_packed" not in e.name and lo <= e.start_ns < hi]
    assert len(kernel) == 1
    assert got["kernel_s"][KERNEL] == pytest.approx(kernel[0] / 1e9)
    assert got["step_other_s"] == pytest.approx(sum(others) / 1e9)
    assert got["step_runs"] == 1
    # the window annotation was cut to 0.3 ms on either side of the program
    assert got["window_s"] == pytest.approx((module.duration_ns + 6e5) / 1e9)
    # ops tile the program's 1,211,022 ns but for 599 ns of gaps
    assert got["busy_s"] == pytest.approx(1_210_423e-9)
    assert list(got["idle_s"]) == ["solve_many"]
    assert got["idle_s"]["solve_many"] == pytest.approx(got["window_s"] - got["busy_s"])
    assert trace.top(got["ops_s"], 1)[0][0] == "jit__frontier_step/rtac_fixpoint_packed.1"
