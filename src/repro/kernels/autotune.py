"""Autotuned instance tiling for the fused fixpoint kernels (DESIGN.md §4).

The fused kernel's schedule is ``block_r``, the rows one grid cell runs to
their fixpoint together. It never changes results (every row's recurrence is
independent and frozen once inactive), only the grid length and how much of
VMEM a cell holds. This module picks the fastest ``block_r`` per shape bucket,
once, and persists the choice.

Mechanics:

- Buckets are ``device_kind/kind/n{n_p}/d{d_p}/r{pow2(R)}``. The device kind
  keeps a schedule timed on one chip (or on the CPU interpreter) from ever
  being applied on another; padded kernel dims are already quantized, and the
  round width R is pow2-bucketed exactly like the frontier's ratcheted widths.
- Candidates are the powers of two up to 8 whose cell fits the kernels' VMEM
  budget (`rtac_support.max_block_r`), so every candidate compiles.
- ``tune``/``ensure_tuned`` time each candidate EAGERLY (block_until_ready on
  a seeded synthetic workload of real `random_csp` networks at the bucket
  shape), in the interpret mode of the backend, and store the winner. Timing
  never happens at jit-trace time.
- The winners persist in a versioned JSON cache (``REPRO_AUTOTUNE_CACHE``
  overrides the path). ``get_config`` — read at trace time by
  `fused_block_r` — READS the in-memory table (loaded from disk once) and
  falls back to the budget's largest ``block_r`` for untuned buckets; it never
  times anything. Tune before first dispatch of a shape (the jitted program
  bakes the schedule it saw): run ``python -m repro.kernels.autotune``, or set
  ``REPRO_AUTOTUNE=1`` to tune on first use.

Cache format (``repro-autotune/v2``)::

    {"schema": "repro-autotune/v2",
     "configs": {"TPU v5 lite/packed/n64/d24/r64": {"block_r": 8}}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Dict, List, Optional

from repro import obs
from repro.core.engine import next_pow2

SCHEMA = "repro-autotune/v2"
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
TUNE_ENV = "REPRO_AUTOTUNE"


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """One fused-kernel schedule; parity-neutral by construction."""

    block_r: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuneConfig":
        return cls(block_r=int(d["block_r"]))


#: in-memory config table, keyed by bucket string; populated by `load_cache`
#: (lazily, once) and by `tune`
_CONFIGS: Dict[str, TuneConfig] = {}
_LOADED: Optional[str] = None  # path the table was loaded from, or None


def cache_path() -> Path:
    override = os.environ.get(CACHE_ENV)
    if override:
        return Path(override)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def bucket_key(kind: str, n_p: int, d_p: int, r: int) -> str:
    """Bucket id on this process's device: kernel dims are already
    padded/quantized; the row count R is pow2-bucketed (the same quantization
    the frontier's ratcheted widths and the service's round padding apply)."""
    return f"{device_kind()}/{kind}/n{n_p}/d{d_p}/r{next_pow2(max(int(r), 1))}"


def load_cache(path: Optional[Path] = None, force: bool = False) -> int:
    """Merge the on-disk cache into the in-memory table (idempotent; corrupt
    or missing files load zero entries). Returns the number of entries."""
    global _LOADED
    p = Path(path) if path is not None else cache_path()
    if _LOADED == str(p) and not force:
        return len(_CONFIGS)
    try:
        payload = json.loads(p.read_text())
        if payload.get("schema") != SCHEMA:
            raise ValueError(f"unknown autotune schema {payload.get('schema')!r}")
        for key, cfg in payload.get("configs", {}).items():
            _CONFIGS[key] = TuneConfig.from_dict(cfg)
    except (OSError, ValueError, KeyError, json.JSONDecodeError):
        pass
    _LOADED = str(p)
    return len(_CONFIGS)


def save_cache(path: Optional[Path] = None) -> Path:
    p = Path(path) if path is not None else cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "schema": SCHEMA,
        "configs": {k: c.to_dict() for k, c in sorted(_CONFIGS.items())},
    }
    p.write_text(json.dumps(payload, indent=2) + "\n")
    return p


def reset(clear_loaded: bool = True) -> None:
    """Drop the in-memory table (tests)."""
    global _LOADED
    _CONFIGS.clear()
    if clear_loaded:
        _LOADED = None


def effective_block_r(block_r: int, r: int) -> int:
    """Largest divisor of ``r`` not exceeding ``block_r`` (the grid needs
    ``block_r | R``; round widths are mostly pow2, so this is usually exact)."""
    br = max(1, min(int(block_r), int(r)))
    while r % br:
        br -= 1
    return br


def _budget_block_r(kind: str, n_p: int, d_p: int) -> int:
    from repro.kernels import rtac_support

    return max(1, rtac_support.max_block_r(kind, n_p, d_p))


def _sanitize(cfg: TuneConfig, kind: str, n_p: int, d_p: int) -> TuneConfig:
    """A cached schedule must still fit this shape's VMEM budget (the cache
    may predate a budget or layout change): clamp ``block_r`` to it."""
    return TuneConfig(max(1, min(cfg.block_r, _budget_block_r(kind, n_p, d_p))))


def get_config(kind: str, n_p: int, d_p: int, r: int) -> TuneConfig:
    """Trace-time schedule lookup — a pure read. Untuned buckets get the
    largest ``block_r`` the VMEM budget allows."""
    if _LOADED is None:
        load_cache()
    cfg = _CONFIGS.get(bucket_key(kind, n_p, d_p, r))
    if cfg is None:
        return TuneConfig(_budget_block_r(kind, n_p, d_p))
    return _sanitize(cfg, kind, n_p, d_p)


def fused_block_r(kind: str, n_p: int, d_p: int, r: int) -> int:
    """The ``block_r`` a fused launch of R rows runs with (``block_r | R``)."""
    return effective_block_r(get_config(kind, n_p, d_p, r).block_r, r)


# ---------------------------------------------------------------------------
# The search — eager timing only, never at trace time
# ---------------------------------------------------------------------------


def candidate_configs(kind: str, n_p: int, d_p: int, r: int) -> List[TuneConfig]:
    """Every distinct effective ``block_r`` among the powers of two up to
    the VMEM budget's largest — each one a schedule that compiles."""
    cap = _budget_block_r(kind, n_p, d_p)
    tiles = sorted({effective_block_r(v, r) for v in (1, 2, 4, 8) if v <= cap})
    return [TuneConfig(br) for br in tiles]


def _tune_workload(kind: str, n_p: int, d_p: int, r: int):
    """A seeded synthetic bucket workload: 3 real `random_csp` networks at
    exactly the padded shape (n_p, d_p are tile multiples, so preparation is
    shape-preserving), r root rows round-robined across them, as kernel
    operands."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import random_csp
    from repro.core.engine import pad_changed
    from repro.kernels import ops

    csps = [random_csp(n_p, d_p, 0.6, 0.5, seed=1000 + i) for i in range(3)]
    prepared = [ops.prepare_network(kind, c) for c in csps]
    if prepared[0][2][:2] != (n_p, d_p):  # pragma: no cover - guarded by callers
        raise ValueError(f"bucket ({n_p}, {d_p}) is not a padded shape: got {prepared[0][2]}")
    idx = np.arange(r, dtype=np.int32) % len(csps)
    net_g = (
        jnp.stack([prepared[j][0][0] for j in idx]),
        jnp.stack([prepared[j][0][1] for j in idx]),
    )
    doms = jnp.stack([prepared[j][1] for j in idx])
    return ops.kernel_operands(net_g, doms, pad_changed(None, n_p, n_p, batch=(r,)))


def _time_candidate(kind: str, d_p: int, operands, cfg: TuneConfig,
                    interpret: bool, repeats: int) -> float:
    import jax

    from repro.kernels import rtac_support

    block_r = effective_block_r(cfg.block_r, operands[1].shape[0])

    def run():
        return rtac_support.fixpoint_rows(
            *operands, encoding=kind, d=d_p, block_r=block_r, interpret=interpret
        )

    jax.block_until_ready(run())  # compile/warm outside the timed window
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def tune(
    kind: str,
    n_p: int,
    d_p: int,
    r: int = 8,
    *,
    repeats: int = 2,
    save: bool = True,
    path: Optional[Path] = None,
) -> TuneConfig:
    """Time every candidate schedule for one bucket (eagerly — never call from
    a traced context) in the backend's interpret mode, record the winner,
    persist the cache. Returns it."""
    from repro.kernels import ops

    if kind not in ("dense", "packed"):
        raise ValueError(f"unknown kernel kind {kind!r}")
    interpret = ops.interpret_mode()
    r = next_pow2(max(int(r), 1))
    t_search0 = time.perf_counter()
    with obs.span("autotune.search", cat="autotune", kind=kind,
                  n=n_p, d=d_p, r=r) as _sp:
        operands = _tune_workload(kind, n_p, d_p, r)
        best_cfg, best_t = None, float("inf")
        candidates = candidate_configs(kind, n_p, d_p, r)
        for cfg in candidates:
            t = _time_candidate(kind, d_p, operands, cfg, interpret, repeats)
            if t < best_t:
                best_cfg, best_t = cfg, t
        if _sp is not None:
            _sp.args["candidates"] = len(candidates)
    obs.counter_add("autotune.tuned_buckets")
    obs.observe("autotune.search_seconds", time.perf_counter() - t_search0)
    _CONFIGS[bucket_key(kind, n_p, d_p, r)] = best_cfg
    if save:
        save_cache(path)
    return best_cfg


def ensure_tuned(kind: str, n_p: int, d_p: int, r: int, **tune_kwargs) -> TuneConfig:
    """Tune the bucket only if the (loaded) cache has no entry for it."""
    if _LOADED is None:
        load_cache(tune_kwargs.get("path"))
    hit = _CONFIGS.get(bucket_key(kind, n_p, d_p, r))
    if hit is not None:
        return hit
    return tune(kind, n_p, d_p, r, **tune_kwargs)


def maybe_tune(kind: str, n_p: int, d_p: int, r: int) -> Optional[TuneConfig]:
    """Engine hook: tune-on-first-use, gated by ``REPRO_AUTOTUNE=1`` (timing
    a bucket in interpret mode is not free, so it is opt-in)."""
    from repro.kernels import rtac_support

    # where no row fits VMEM the launch streams one row a cell: no block_r
    if not os.environ.get(TUNE_ENV) or rtac_support.max_block_r(kind, n_p, d_p) == 0:
        return None
    return ensure_tuned(kind, n_p, d_p, r)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Tune the fused fixpoint's block_r for one bucket"
    )
    ap.add_argument("--kind", choices=("dense", "packed"), default="packed")
    ap.add_argument("--n", type=int, default=16, help="padded var count n_p")
    ap.add_argument("--d", type=int, default=8, help="padded domain size d_p")
    ap.add_argument("--rows", type=int, default=8, help="round width R")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--cache", type=Path, default=None,
                    help=f"cache file (default: ${CACHE_ENV} or "
                         f"~/.cache/repro/autotune.json)")
    args = ap.parse_args(argv)
    if args.cache is not None:
        os.environ[CACHE_ENV] = str(args.cache)
    load_cache(args.cache)
    cfg = tune(args.kind, args.n, args.d, args.rows,
               repeats=args.repeats, path=args.cache)
    key = bucket_key(args.kind, args.n, args.d, args.rows)
    print(json.dumps({"bucket": key, "config": cfg.to_dict(),
                      "cache": str(args.cache or cache_path())}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
