"""What the per-layer metric files read from the program's own spans.

Two kinds, from the record a traced run leaves (`readers`):

- host time in a span, from ``spans`` (the span durations the program's
  tracer recorded over the traced part): ``service.admit`` and ``round.wait``
  a lockstep round, the mean ``slot.install``;
- device-idle time under a span, from the run's profiler trace, split over
  the program's spans by `span_trace.split`, as a share of the traced window.

Each reader returns None when the run left it nothing to read: a span the
program never opened, or a trace that holds none of its spans.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

from . import cells, span_trace, trace

#: where `run.trace_dir` keeps each cell's trace; the newest one under it is
#: the run's own, as a traced run reads its metrics right after its trace
TRACE_ROOT = cells.ROOT / ".bench_trace"


def _ms_per_round(rec: dict, name: str) -> Optional[float]:
    spans, rounds = rec["spans"].get(name), int(rec["counters"].get("driver.rounds", 0))
    return 1e3 * sum(spans) / rounds if spans and rounds else None


def admit_ms_per_round(rec: dict) -> Optional[float]:
    """Host time admitting requests (``service.admit``) a lockstep round."""
    return _ms_per_round(rec, "service.admit")


def resolve_wait_ms_per_round(rec: dict) -> Optional[float]:
    """Host time blocked on a round's metadata (``round.wait``) a round."""
    return _ms_per_round(rec, "round.wait")


def install_ms(rec: dict) -> Optional[float]:
    """Mean host duration of a network's install into its slot."""
    spans = rec["spans"].get("slot.install")
    return 1e3 * sum(spans) / len(spans) if spans else None


@functools.lru_cache(maxsize=2)
def _split_file(path: str, _mtime_ns: int, names: frozenset) -> dict:
    from jax.profiler import ProfileData

    return span_trace.split(ProfileData.from_file(path), names)


def idle_split(rec: dict) -> Optional[dict]:
    """The run's idle split over the program's spans, or None: untraced, no
    trace found, a program that declares no spans or opened none in the
    trace, or a trace whose idle time is not the record's."""
    from repro import obs

    tr, names = rec.get("trace"), getattr(obs, "SPANS", None)
    if not tr or names is None:
        return None
    try:
        path = trace.find_xplane(str(TRACE_ROOT))
    except FileNotFoundError:
        return None
    got = _split_file(path, os.stat(path).st_mtime_ns, frozenset(names))
    same = math.isclose(sum(got["idle_by_span_s"].values()), sum(tr["idle_s"].values()),
                        rel_tol=1e-6, abs_tol=1e-9)
    return got if got["program_spans"] and same else None


def _idle_under_pct(rec: dict, name: str) -> Optional[float]:
    got = idle_split(rec)
    if got is None or not got["window_s"]:
        return None
    return 100.0 * got["idle_under_s"].get(name, 0.0) / (got["window_s"] * got["devices"])


def idle_in_admit_pct(rec: dict) -> Optional[float]:
    """Device-idle time under ``service.admit`` or a child of it, as a share
    of the traced window."""
    return _idle_under_pct(rec, "service.admit")


def idle_in_round_pct(rec: dict) -> Optional[float]:
    """Device-idle time under ``driver.round`` or a child of it."""
    return _idle_under_pct(rec, "driver.round")
