"""Benchmark orchestrator — one function per paper table/figure or subsystem.

Prints ``name,...`` CSV rows. Quick mode keeps CPU runtime in minutes; pass
--full for the paper's complete grid (n up to 1000).

  engines  per-engine enforce latency on 3 problem families × 3 sizes ->
           BENCH_engines.json (the cross-PR perf trajectory)
  many     instances/second of solve_many vs sequential mac_solve ->
           BENCH_engines.json "many" section
  service  SolverService trace replay: sustained throughput + tail latency ->
           BENCH_engines.json "service" section
  sweeps   the committed `repro.sweeps` studies (resume-aware: completed
           cells in results/ are never re-run) -> ungated "sweeps" section
           + a per-sweep history row. The paper's Table 1 / Fig. 3
           protocols live here now, as the ``recurrence_density``
           assignments-mode sweep (formerly the table1/fig3 targets).

``--only <target>`` runs one target; an unknown target exits non-zero and
prints the valid target list (no more silently running nothing on a typo).
"""

from __future__ import annotations

import argparse
import sys


def _run_engines(quick: bool) -> None:
    from . import bench_engines

    bench_engines.main()


def _run_many(quick: bool) -> None:
    from . import bench_many

    bench_many.main()


def _run_service(quick: bool) -> None:
    from . import bench_service

    bench_service.main(quick=quick)


def _run_sweeps(quick: bool) -> None:
    from repro.sweeps import available_specs, load_cells, load_spec, run_spec

    from . import tracker

    rows = []
    for name in available_specs():
        if name == "smoke":  # CI fixture, not a study
            continue
        spec = load_spec(name)
        d = run_spec(spec)  # resume-aware; a complete study is a no-op
        records = load_cells(d / "cells.jsonl")
        secs = sorted(r["cell_seconds"] for r in records)
        row = {
            "sweep": name,
            "mode": spec.mode,
            "n_cells": len(records),
            "total_seconds": round(sum(secs), 3),
            "median_cell_seconds": round(secs[len(secs) // 2], 3) if secs else 0.0,
        }
        rows.append(row)
        print(f"sweeps,{name},{spec.mode},{row['n_cells']},"
              f"{row['total_seconds']:.1f}s")
    tracker.merge_section("sweeps", rows)
    print(f"sweeps: wrote {tracker.OUT_PATH}")


#: registration order is execution order for a full run
TARGETS = {
    "engines": _run_engines,
    "many": _run_many,
    "service": _run_service,
    "sweeps": _run_sweeps,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale grid")
    ap.add_argument("--only", default=None, metavar="TARGET",
                    help=f"run one target; valid: {', '.join(TARGETS)}")
    args = ap.parse_args(argv)
    quick = not args.full

    if args.only is not None and args.only not in TARGETS:
        print(
            f"benchmarks.run: unknown target {args.only!r}; "
            f"valid targets: {', '.join(TARGETS)}",
            file=sys.stderr,
        )
        return 2
    from repro.launch import compile_cache

    compile_cache.enable()
    for name, fn in TARGETS.items():
        if args.only in (None, name):
            fn(quick)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
