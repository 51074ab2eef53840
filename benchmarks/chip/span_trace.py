"""Put a profiler trace's device-idle gaps down to the program's own spans.

`repro.obs` opens a `jax.profiler.TraceAnnotation` for every span while its
tracer is on, so a traced run's host plane holds the program's spans
(``service.admit``, ``driver.round``, ``round.wait``, ...) beside the
harness's annotations (`trace.HARNESS_SPANS`), on the device planes' clock.
Program spans are recognised by the names the program declares
(``repro.obs.SPANS``); JAX's own host events are never taken for them.

Each idle gap of the window goes to the harness annotation that holds its
midpoint, exactly as `trace.reduce` puts it in ``idle_s``. Within that, the
gap is split by intersection over the innermost program span holding each
part of it, keyed ``<harness>/<span>`` (``SolverService.step/slot.install``);
a part no program span holds keeps the bare harness name. So the keys of
``idle_by_span_s`` summed by harness prefix equal ``idle_s``.
``idle_under_s`` counts, for each span name, the idle time with that span
anywhere among the open spans: the time under it or one of its children.

    python -m benchmarks.chip.span_trace .bench_trace/<cell>

prints the split of the newest trace under a directory as JSON.
"""

from __future__ import annotations

import bisect
import json
import sys
import warnings
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

from . import trace

Segment = Tuple[float, float, Tuple[str, ...]]  # (start_ns, end_ns, open spans, outermost first)


def segments(spans: Iterable[Tuple[str, float, float]]) -> List[Segment]:
    """Cut the host timeline at every span edge: each piece with the spans
    open over it, outermost first. Pieces no span holds are left out."""
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    starts = [s for _, s, _ in spans]
    out: List[Segment] = []
    open_: List[int] = []
    nxt = 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(spans) and starts[nxt] <= a:
            open_.append(nxt)
            nxt += 1
        open_ = [i for i in open_ if spans[i][2] > a]
        if open_:
            out.append((a, b, tuple(spans[i][0] for i in open_)))
    return out


def split(profile, names: Iterable[str]) -> Dict:
    """The idle split of a `ProfileData` over the program spans ``names``."""
    with warnings.catch_warnings():
        # jaxlib's stats iterator warns about its own type on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        return _split(profile, frozenset(names))


def _split(profile, names: frozenset) -> Dict:
    harness: List[Tuple[str, float, float]] = []
    program: List[Tuple[str, float, float]] = []
    devices: List[list] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in trace.HARNESS_SPANS:
                        harness.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                    elif ev.name in names:
                        program.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif (plane.name.startswith(trace.DEVICE_PREFIX)
              and plane.name[len(trace.DEVICE_PREFIX):].isdigit()):
            devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for line in plane.lines if line.name == trace.OPS_LINE
                            for ev in line.events])
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    windows = [(s, e) for name, s, e in harness if name == trace.WINDOW]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        window = (min(s for ops in devices for s, _ in ops),
                  max(e for ops in devices for _, e in ops))
    name_at = trace._attribute([a for a in harness if a[0] != trace.WINDOW])
    pieces = segments(program)
    piece_ends = [b for _, b, _ in pieces]

    by_span: Dict[str, float] = defaultdict(float)
    under: Dict[str, float] = defaultdict(float)
    for ops in devices:
        clipped = (trace._clip(s, e, window) for s, e in ops)
        merged = trace.union(c for c in clipped if c is not None)
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            prefix = name_at((s + e) / 2)
            held = 0.0  # ns of the gap that program spans hold
            i = bisect.bisect_right(piece_ends, s)
            while i < len(pieces) and pieces[i][0] < e:
                a, b, chain = pieces[i]
                part = min(b, e) - max(a, s)
                by_span[f"{prefix}/{chain[-1]}"] += part / 1e9
                for name in set(chain):
                    under[name] += part / 1e9
                held += part
                i += 1
            if (e - s) - held > 1e-3:  # below a picosecond is rounding
                by_span[prefix] += ((e - s) - held) / 1e9
    return {
        "devices": len(devices),
        "window_s": (window[1] - window[0]) / 1e9,
        "program_spans": len(program),
        "idle_by_span_s": dict(by_span),
        "idle_under_s": dict(under),
    }


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    from repro import obs

    (log_dir,) = sys.argv[1:] if argv is None else argv
    got = split(ProfileData.from_file(trace.find_xplane(log_dir)), obs.SPANS)
    got["idle_by_span_s"] = trace.top(got["idle_by_span_s"], 40)
    got["idle_under_s"] = trace.top(got["idle_under_s"], 40)
    print(json.dumps(got, indent=1))
    return 0


if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root / "src"), str(root)]
    sys.exit(main())
