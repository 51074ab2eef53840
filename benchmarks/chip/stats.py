"""The benchmark's own arithmetic: percentiles, spreads and chip peaks."""

from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Iterable, Sequence

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def percentile(samples: Iterable[float], pct: float) -> float:
    """Linear-interpolated percentile; 0.0 over no samples. An infinite
    sample (a request that never finished) makes every percentile it
    reaches infinite."""
    arr = np.fromiter(samples, dtype=float)
    if arr.size == 0:
        return 0.0
    arr.sort()
    pos = (arr.size - 1) * pct / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(arr[hi]):
        return math.inf
    return float(arr[lo] + (arr[hi] - arr[lo]) * (pos - lo))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def peaks(device_kind: str) -> dict:
    """The published peaks of one device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
