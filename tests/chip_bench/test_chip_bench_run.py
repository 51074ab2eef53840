"""A run of each entry off the chip, at a tiny size: the result line, the
timing from due times, whole-batch windows, and the refusal of any platform
but a TPU."""

from __future__ import annotations

import contextlib
import json
import math
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest
from conftest import tiny_suite

from benchmarks.chip import cells, run, traffic

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_suite(tmp_path_factory.mktemp("tiny"), "pallas_packed")


@pytest.mark.parametrize("workload, metrics", [
    ("frb50-poisson", {"p50_latency_ms", "served_rps", "setup_s"}),
    ("frb50-zipf", {"p95_latency_ms", "p50_latency_ms", "served_rps", "setup_s"}),
    ("frb100-batch24", {"solve_rate", "setup_s"}),
])
def test_a_tiny_run_is_correct_and_reports_its_metrics(tiny, workload, metrics):
    cell = cells.load(workload, root=tiny)
    out = run.drive(cell, SEED, 1.0, False, time.monotonic())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 and math.isfinite(m["value"]) for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert {c["limit"] for c in out["checks"].values()} == {0}
    assert out["device"]["count"] == 1
    json.dumps(out)


def _request(finished, done=True, admitted=None):
    from repro.service.service import RequestStatus

    return types.SimpleNamespace(
        finished_at=finished, admitted_at=admitted,
        status=RequestStatus.DONE if done else RequestStatus.FAILED)


def test_latency_runs_from_the_due_time_and_failures_are_infinitely_late():
    t0 = 100.0
    arrivals = [traffic.Arrival(float(i), (0, 0, i)) for i in range(20)]
    # each request answered 0.5 s after it was due, and submitted late does
    # not matter: the clock starts at the due time
    reqs = [_request(t0 + a.due + 0.5) for a in arrivals]
    metrics, done = run.service_metrics(t0, arrivals, reqs, 20.0)
    assert metrics["p50_latency_ms"] == pytest.approx(500.0)
    assert metrics["served_rps"] == pytest.approx(20 / 20.0)
    assert all(done)
    # one failed request in twenty: the 95th percentile reaches it
    reqs[7] = _request(t0 + 7.5, done=False)
    reqs[3] = None  # never submitted
    metrics, done = run.service_metrics(t0, arrivals, reqs, 20.0)
    assert metrics["p95_latency_ms"] == run.NEVER
    assert metrics["p50_latency_ms"] == pytest.approx(500.0)
    assert done.count(False) == 2 and metrics["served_rps"] == pytest.approx(18 / 20.0)


def test_answers_after_the_close_count_in_the_tail_not_the_served_rate():
    t0 = 0.0
    arrivals = [traffic.Arrival(9.0, (0, 0, 0)), traffic.Arrival(1.0, (0, 0, 1))]
    reqs = [_request(12.0), _request(1.25)]
    metrics, _ = run.service_metrics(t0, arrivals, reqs, 10.0)
    assert metrics["served_rps"] == pytest.approx(1 / 10.0)
    assert metrics["p95_latency_ms"] == pytest.approx(250.0 + (3000.0 - 250.0) * 0.95)


def test_batch_window_ends_on_the_batch_that_crosses_it():
    solved = []

    def solve(batch):
        time.sleep(0.03)
        solved.append(batch)
        return batch, batch

    count = iter(range(1000))
    durations, waits = run.closed_loop(lambda: [next(count)] * 4, solve,
                                       lambda b, r: None, 0.1,
                                       lambda _name: contextlib.nullcontext())
    assert len(durations) == len(solved) == len(waits)
    assert sum(durations[:-1]) < 0.1 <= sum(durations) + sum(waits)
    assert all(len(b) == 4 for b in solved)  # whole batches only


def test_any_platform_but_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip", "--workload", "frb50-poisson",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
