"""What the per-layer metric files read from a run's record.

A record is what one run gathered over its window: ``counters`` (the
program's `repro.obs` registry deltas), ``spans`` (the program's span
durations in seconds, traced runs only), ``trace`` (the profiler trace
reduced by `trace.reduce`, traced runs only), ``kernel`` with
``kernel_row_bytes`` and ``hbm_bytes_per_s``, ``compiles_in_window``, and
for the service ``queue_wait_s``. Each reader returns None when the run left
it nothing to read, and the metric is then left out of the result line.
"""

from __future__ import annotations

from typing import Optional

from . import stats


def _rounds(rec: dict) -> int:
    return int(rec["counters"].get("driver.rounds", 0))


def queue_wait_ms(rec: dict) -> Optional[float]:
    """p95 of the time from a request's due time to its admission."""
    waits = rec.get("queue_wait_s")
    return 1e3 * stats.percentile(waits, 95) if waits else None


def cache_hit_pct(rec: dict) -> Optional[float]:
    c = rec["counters"]
    hits, misses = c.get("cache.hits", 0), c.get("cache.misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None


def round_ms(rec: dict) -> Optional[float]:
    """Mean host duration of the lockstep driver's ``driver.round`` span."""
    spans = rec["spans"].get("driver.round")
    return 1e3 * sum(spans) / len(spans) if spans else None


def rows_per_round(rec: dict) -> Optional[float]:
    rounds = _rounds(rec)
    return rec["counters"].get("driver.rows", 0) / rounds if rounds else None


def frontier_kib_per_round(rec: dict) -> Optional[float]:
    c, rounds = rec["counters"], _rounds(rec)
    if not rounds:
        return None
    return (c.get("frontier.h2d_bytes", 0) + c.get("frontier.d2h_bytes", 0)) / rounds / 1024


def _kernel_s(rec: dict) -> float:
    return rec.get("trace", {}).get("kernel_s", {}).get(rec.get("kernel"), 0.0)


def kernel_ms_per_round(rec: dict) -> Optional[float]:
    seconds, rounds = _kernel_s(rec), _rounds(rec)
    return 1e3 * seconds / rounds if seconds and rounds else None


def kernel_roofline_pct(rec: dict) -> Optional[float]:
    """The kernel's least HBM bytes (every row it was given, at its shape)
    over the peak bandwidth, as a share of its device time."""
    seconds = _kernel_s(rec)
    rows = rec["counters"].get("driver.rows", 0)
    if not seconds or not rows:
        return None
    least_s = rows * rec["kernel_row_bytes"] / rec["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds


def step_other_ms_per_round(rec: dict) -> Optional[float]:
    tr, rounds = rec.get("trace", {}), _rounds(rec)
    if not tr.get("step_runs") or not rounds:
        return None
    return 1e3 * tr["step_other_s"] / rounds


def device_idle_pct(rec: dict) -> Optional[float]:
    tr = rec.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def compiles_in_window(rec: dict) -> Optional[float]:
    return float(rec["compiles_in_window"])
