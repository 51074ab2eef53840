"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel time,
the frontier step's other device time and the idle gaps by host activity.

The trace is read with `jax.profiler.ProfileData`. Device planes are those
named ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per device
operation and their ``XLA Modules`` line one per program run. Host planes
(``/host:...``) hold the benchmark's `jax.profiler.TraceAnnotation` spans:
one named ``window`` around the measured part, and one around each call the
harness makes into the system (``submit``, ``SolverService.step``,
``solve_many``, ``producer.wait``, ...). Everything is clipped to the window.

- busy: the union of the device-op intervals, averaged over the devices;
- ops: device seconds by program and op name (``jit__frontier_step/copy.65``);
- kernel: device seconds of the ops whose name or HLO stats name the kernel;
- step_other: device seconds of the other ops run inside programs whose
  name holds ``_frontier_step`` (``step_runs`` counts those programs' runs);
- idle: the gaps between device ops inside the window, each put down to the
  harness annotation that holds its midpoint (``untraced`` if none), summed
  by annotation.
"""

from __future__ import annotations

import bisect
import glob
import os
import warnings
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "window"
#: the annotations the harness wraps around its calls into the system
HARNESS_SPANS = frozenset({
    WINDOW, "submit", "SolverService.step", "arrival.wait",
    "producer.wait", "solve_many", "check.solutions",
})
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"

Interval = Tuple[float, float]  # (start_ns, end_ns)


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a `jax.profiler.trace` directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _label(event) -> str:
    """An op's name with its string stats (HLO op, module, long name), so a
    kernel is found whichever field carries its name."""
    parts = [event.name]
    for _key, value in event.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def _attribute(harness: List[Tuple[str, float, float]]):
    """A lookup from a time to the harness annotation that holds it. The
    harness makes its calls one after another on one thread, so its
    annotations (the window aside) do not overlap."""
    harness = sorted(harness, key=lambda a: a[1])
    starts = [a[1] for a in harness]

    def name_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return harness[i][0] if i >= 0 and harness[i][2] >= t else "untraced"

    return name_at


def _program_at(programs: List[Tuple[float, float, str]]):
    """A lookup from a time to the program (``XLA Modules`` event) running
    then, by name without its hash: ``jit__frontier_step``."""
    programs = sorted(programs)
    starts = [p[0] for p in programs]

    def name_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return programs[i][2] if i >= 0 and programs[i][1] >= t else ""

    return name_at


def op_name(event_name: str) -> str:
    """``%copy.65 = pred[...] copy(...)`` -> ``copy.65``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(profile, kernels: Sequence[str], step_module: str = "_frontier_step") -> Dict:
    """Reduce a `ProfileData` to the quantities above (seconds)."""
    with warnings.catch_warnings():
        # jaxlib's stats iterator warns about its own type on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        return _reduce(profile, kernels, step_module)


def _reduce(profile, kernels: Sequence[str], step_module: str) -> Dict:
    annotations: List[Tuple[str, float, float]] = []
    devices: List[list] = []
    modules: List[list] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        annotations.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX):].isdigit():
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(line.events)
                elif line.name == MODULES_LINE:
                    mods.extend(line.events)
            devices.append(ops)
            modules.append(mods)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    windows = [(s, e) for name, s, e in annotations if name == WINDOW]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        spans = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ops in devices for ev in ops]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    name_at = _attribute([a for a in annotations if a[0] != WINDOW])

    ops_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    idle_s: Dict[str, float] = defaultdict(float)
    busy_ns = step_other_ns = 0.0
    step_count = 0
    for ops, mods in zip(devices, modules):
        program_at = _program_at([
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(", 1)[0])
            for ev in mods
        ])
        step_count += sum(step_module in ev.name and _clip(
            ev.start_ns, ev.start_ns + ev.duration_ns, window) is not None for ev in mods)
        intervals = []
        for ev in ops:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window)
            if c is None:
                continue
            dur = (c[1] - c[0]) / 1e9
            intervals.append(c)
            program = program_at((c[0] + c[1]) / 2)
            ops_s[f"{program}/{op_name(ev.name)}" if program else op_name(ev.name)] += dur
            label = _label(ev)
            hit = next((k for k in kernels if k in label), None)
            if hit is not None:
                kernel_s[hit] += dur
            elif step_module in program:
                step_other_ns += c[1] - c[0]
        merged = union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle_s[name_at((s + e) / 2)] += (e - s) / 1e9
    n = len(devices)
    return {
        "devices": n,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "ops_s": dict(ops_s),
        "kernel_s": dict(kernel_s),
        "step_other_s": step_other_ns / 1e9,
        "step_runs": step_count,
        "idle_s": dict(idle_s),
    }


def top(table: Dict[str, float], k: int = 10) -> List[list]:
    """The ``k`` largest entries as ``[name, seconds]`` pairs."""
    return [[name, s] for name, s in sorted(table.items(), key=lambda kv: -kv[1])[:k]]
