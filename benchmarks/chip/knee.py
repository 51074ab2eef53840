"""Sweep the offered rate of an open-loop cell on the chip to find its knee.

    python -m benchmarks.chip.knee --workload frb50-poisson --seed <n> \
        --seconds 10 --repeats 3 --rates 8,12,16,20,24,28,32

One process: the cell's service is set up and warmed once, then each rate,
in ascending order, runs ``--repeats`` windows of ``--seconds`` of fresh
arrivals (seed stream ``(WINDOW, k, j)`` for the j-th repeat of the k-th
rate), each drained before the next. Per window it prints one JSON line: the
offered and served rates, the latency percentiles from due times, and the
median latency of each quarter of the window's requests.

A window holds when every request is answered and its last quarter's median
latency is at most `GROWTH` times its second quarter's: the queue did not
grow over the window. (The first quarter is left out of the comparison: it
arrives at a service that the previous window left idle, and waits less.)
The knee is the highest rate below the first rate with a window that does
not hold (the sweep stops there), or the highest rate swept when every
window holds: then the true knee lies at or above it. The last line names
it. The benchmark's open-loop cells run at 4/5 of it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import cells, run, traffic  # noqa: E402


#: how much longer the last quarter of a window may wait than its second
#: before the queue counts as growing: two quarters of a steady window differ
#: by noise alone, and a growing queue multiplies the wait
GROWTH = 1.25


def holds(row: dict) -> bool:
    """A window in which the service kept pace with the offered rate."""
    return (row["unanswered"] == 0
            and row["quarter_p50_ms"][3] <= GROWTH * row["quarter_p50_ms"][1])


def knee_of(rows) -> float:
    """The highest offered rate below the first rate with a window that does
    not hold, or the highest rate swept when none fails (0.0 when the lowest
    rate fails)."""
    knee = 0.0
    for rate in sorted({r["offered_rps"] for r in rows}):
        if not all(holds(r) for r in rows if r["offered_rps"] == rate):
            break
        knee = rate
    return knee


def sweep(cell, seed: int, seconds: float, rates, repeats: int):
    from repro.service import SolverService

    cfg = cell.config
    budget = int(cfg["search"]["max_assignments"])
    svc = SolverService(engine=cfg["engine"], **cfg.get("service", {}))
    run.warm_service(svc, cfg, cell.traffic, seed, budget)
    for k, rate in enumerate(sorted(rates)):
        for j in range(repeats):
            mix = dict(cell.traffic, rate_rps=rate)
            arrivals = traffic.open_loop(seed, (traffic.WINDOW, k, j), mix, seconds)
            built = {}
            run._instances(cfg, arrivals, built)
            t0, reqs, late = run.drive_open_loop(
                svc, arrivals, built, budget, lambda _n: contextlib.nullcontext())
            metrics, done = run.service_metrics(t0, arrivals, reqs, seconds)
            lat = np.asarray([r.finished_at - (t0 + a.due) if ok else np.inf
                              for r, ok, a in zip(reqs, done, arrivals)])
            row = {"offered_rps": rate, "repeat": j, **metrics, "requests": len(reqs),
                   "unanswered": done.count(False),
                   "quarter_p50_ms": [1e3 * float(np.median(q))
                                      for q in np.array_split(lat, 4)],
                   "generator_late_max_ms": 1e3 * float(late.max())}
            row["holds"] = holds(row)
            yield row
            if not row["holds"]:
                return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    import jax
    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    t0 = time.monotonic()
    rows = []
    for row in sweep(cell, args.seed, args.seconds,
                     [float(r) for r in args.rates.split(",")], args.repeats):
        row["t_s"] = time.monotonic() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = knee_of(rows)
    print(json.dumps({"knee_rps": knee, "cell_rate_rps": 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
