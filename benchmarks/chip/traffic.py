"""The one traffic generator: instances and arrival schedules from a seed.

Everything a cell sends is a pure function of ``--seed`` and the data files
of its configuration and traffic mix, so a later change to the program cannot
move what is offered:

- `model_rb` builds one Xu–Li Model RB instance in numpy (the benchmark's own
  copy of the generator: d = ⌈n^alpha⌉, m = ⌈r·n·ln n⌉ distinct scopes,
  exactly round(p·d²) disallowed tuples per constraint); `instance` builds
  one at a configuration's ``problem`` parameters.
- `open_loop` gives the due times of an open-loop stream and which instance
  each request carries: Poisson arrivals conditioned on their count (rate ×
  seconds, so every seed offers the same number of requests), over unique
  instances or a Zipf-skewed pool.
- `batch_instances` gives the instances of one closed-loop batch.

Seeds are numpy ``SeedSequence`` entropy lists ``[seed, stream, ...]``:
``stream`` keeps the measured window, the warm-up and the shared pool apart.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple, Sequence

import numpy as np

#: seed streams: the measured traffic, the warm-up traffic, the shared pool,
#: the sample of answers held to the reference
WINDOW, WARMUP, POOL, CHECK = 0, 1, 2, 3


class Instance(NamedTuple):
    """One binary CSP as host arrays (the shapes `repro.core.csp.CSP` holds)."""

    cons: np.ndarray  # (n, n, d, d) bool, allowed value pairs
    mask: np.ndarray  # (n, n) bool, constrained pairs
    dom: np.ndarray  # (n, d) bool


def model_rb_shape(n: int, alpha: float, r: float):
    """(d, m) of Model RB at (n, alpha, r)."""
    d = max(2, math.ceil(n**alpha))
    m = min(math.ceil(r * n * math.log(n)), n * (n - 1) // 2)
    return d, m


def model_rb(entropy: Sequence[int], n: int, alpha: float, r: float,
             p: float) -> Instance:
    """One Model RB instance of tightness ``p``, seeded by ``entropy``."""
    rng = np.random.default_rng(list(entropy))
    d, m = model_rb_shape(n, alpha, r)
    q = int(round(p * d * d))
    if not 0 <= q <= d * d:
        raise ValueError(f"tightness {p} gives {q} disallowed tuples of {d * d}")
    xs, ys = np.triu_indices(n, k=1)
    pick = rng.choice(len(xs), size=m, replace=False)
    xs, ys = xs[pick], ys[pick]
    # the q smallest of d² uniform keys are the disallowed tuples
    keys = rng.random((m, d * d))
    if q:
        allowed = keys > np.partition(keys, q - 1, axis=1)[:, q - 1:q]
    else:
        allowed = np.ones((m, d * d), dtype=bool)
    rel = allowed.reshape(m, d, d)
    cons = np.zeros((n, n, d, d), dtype=bool)
    cons[xs, ys] = rel
    cons[ys, xs] = rel.transpose(0, 2, 1)
    mask = np.zeros((n, n), dtype=bool)
    mask[xs, ys] = True
    mask[ys, xs] = True
    return Instance(cons, mask, np.ones((n, d), dtype=bool))


def instance(problem: dict, entropy: Sequence[int]) -> Instance:
    """The instance of ``entropy`` at a configuration's ``problem``."""
    return model_rb(entropy, problem["n"], problem["alpha"], problem["r"], problem["p"])


class Arrival(NamedTuple):
    due: float  # seconds after the window opens
    instance: tuple  # entropy of the instance this request carries


def zipf_ranks(rng, count: int, pool: int, s: float) -> np.ndarray:
    """``count`` draws of ranks 0..pool-1 with P(k) ∝ 1 / (k + 1)^s."""
    weights = 1.0 / np.arange(1, pool + 1, dtype=float) ** s
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(count), side="right"), pool - 1)


def open_loop(seed: int, stream: tuple, mix: dict, seconds: float) -> List[Arrival]:
    """The arrivals of one open-loop window (or warm-up) of ``seconds``;
    ``stream`` is the seed stream's tuple, such as ``(WINDOW,)``."""
    rng = np.random.default_rng([seed, *stream])
    count = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    # Poisson arrivals conditioned on their count: sorted uniforms
    due = np.sort(rng.uniform(0.0, seconds, count))
    pool = mix.get("pool")
    if pool is None:
        return [Arrival(float(t), (seed, *stream, i)) for i, t in enumerate(due)]
    # a pool shared by the window and its warm-up: rank k is instance k
    ranks = zipf_ranks(rng, count, int(pool["size"]), float(pool["zipf_s"]))
    return [Arrival(float(t), (seed, POOL, int(k))) for t, k in zip(due, ranks)]


def batch_instances(seed: int, stream: int, index: int, size: int) -> List[tuple]:
    """Entropy of the ``size`` instances of batch ``index``."""
    return [(seed, stream, index, i) for i in range(size)]
