#!/usr/bin/env python3
"""Chip smoke: the solver's main path, end to end, on a TPU.

One chip (the default): 16 Model RB instances at the width of the public
BHOSLIB frb50-23 series (n=50, alpha=0.8 so d=23, hardness 1.0), made from
``--seed``, run through every device engine — ``einsum``, ``pallas_packed``
and ``pallas_dense`` (fused fixpoint) — in three phases each:

  closures    root AC closures, ``get_engine(e).prepare(csp).enforce()``
  solve_many  the 16 searches in lockstep, ``--budget`` assignments each
  service     the same 16 replayed through ``SolverService``

Every phase is held to the host AC3 engine, which does not use JAX: closures,
verdicts, solutions, ``n_assignments`` and ``n_backtracks`` must equal it.
On the chip, a Pallas engine must run compiled (``interpret is False``) and
its frontier step must hold the kernel (``tpu_custom_call``), and the service
must finish with no demotion, failure or shed, every bucket on ladder level 0.

``--chips 4`` runs only the sharded phase: ``ShardedEngine`` (``einsum`` and
``bitpacked``) over a mesh of the 4 chips, on a Model RB network of n=1024,
d=32 (a 1 GiB constraint tensor), its closures of perturbed domains compared
with the one-chip ``einsum`` engine's, and the constraint shards checked to
sit on 4 distinct devices.

Each phase prints one JSON line; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. The script refuses
any platform but ``tpu`` (exit 1). Everything runs in this one process.

    python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ENGINES = ("einsum", "pallas_packed", "pallas_dense")


# ---------------------------------------------------------------------------
# Workload and oracle
# ---------------------------------------------------------------------------


def workload(seed: int, count: int = 16, n: int = 50, alpha: float = 0.8,
             hardness: float = 1.0):
    """``count`` Model RB instances sharing (n, d); instance i seeded (seed, i)."""
    from repro.problems import generate_batch

    return generate_batch("model_rb", count, seed=seed, n=n, alpha=alpha, hardness=hardness)


def ac3_oracle(csps, budget: int):
    """Closures and searches from the host AC3 engine (numpy, no JAX)."""
    import numpy as np

    from repro.core.search import mac_solve
    from repro.engines import get_engine

    ac3 = get_engine("ac3")
    closures = []
    for c in csps:
        res = ac3.prepare(c).enforce()
        closures.append((np.asarray(res.dom), bool(res.consistent)))
    searches = [mac_solve(c, engine="ac3", max_assignments=budget) for c in csps]
    return closures, searches


def _search_key(sol, stats):
    return (sol, stats.n_assignments, stats.n_backtracks, stats.exhausted)


def _searches_agree(got, oracle) -> bool:
    return all(_search_key(*g) == _search_key(*o) for g, o in zip(got, oracle))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _twice(fn):
    """(first-call seconds incl. compilation, warm wall seconds, result)."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return first, time.perf_counter() - t0, out


def _record(engine, phase, csps, first, wall, agree, **extra):
    import jax

    return {
        "engine": engine, "phase": phase,
        "device_kind": jax.devices()[0].device_kind,
        "first_call_s": first, "wall_s": wall,
        "instances": len(csps), "agree": bool(agree),
        "peak_bytes_in_use": _peak_bytes(), **extra,
    }


def _engine(name):
    from repro.engines import get_engine

    return get_engine(name)


def phase_closures(name, csps, oracle_closures) -> dict:
    import jax
    import numpy as np

    eng = _engine(name)

    def run():
        return jax.block_until_ready([eng.prepare(c).enforce() for c in csps])

    first, wall, results = _twice(run)
    agree = all(
        bool(r.consistent) == ok and (not ok or np.array_equal(np.asarray(r.dom), dom))
        for r, (dom, ok) in zip(results, oracle_closures)
    )
    return _record(name, "closures", csps, first, wall, agree,
                   interpret=getattr(eng, "interpret", None))


def phase_solve_many(name, csps, oracle_searches, budget) -> dict:
    from repro.core.search import solve_many

    eng = _engine(name)
    tel = {}

    def run():
        tel.clear()
        return solve_many(csps, engine=eng, max_assignments=budget, telemetry=tel)

    first, wall, (sols, stats) = _twice(run)
    agree = _searches_agree(list(zip(sols, stats)), oracle_searches)
    return _record(name, "solve_many", csps, first, wall, agree,
                   rounds=tel.get("rounds"), rows_dispatched=tel.get("rows_dispatched"),
                   frontier_custom_call=frontier_custom_call(eng, csps))


def phase_service(name, csps, oracle_searches, budget) -> dict:
    from repro.service import SolverService

    eng = _engine(name)
    last = {}

    def run():
        svc = SolverService(engine=eng, initial_slots=len(csps))
        reqs = [svc.submit(c, max_assignments=budget) for c in csps]
        svc.run_until_idle()
        last["snap"] = svc.snapshot()
        return [r.result() for r in reqs]

    first, wall, results = _twice(run)
    snap = last["snap"]
    healthy = (
        snap["demotions"] == snap["failed"] == snap["shed"] == 0
        and all(b["level"] == 0 for b in snap["buckets"].values())
    )
    agree = healthy and _searches_agree(results, oracle_searches)
    return _record(name, "service", csps, first, wall, agree,
                   demotions=snap["demotions"], failed=snap["failed"], shed=snap["shed"],
                   levels=sorted({b["level"] for b in snap["buckets"].values()}),
                   rounds=snap["rounds"],
                   mean_rows_per_dispatch=snap["mean_rows_per_dispatch"])


def frontier_custom_call(eng, csps, rows: int = 16):
    """Whether the engine's compiled frontier step holds a Mosaic kernel
    (None for engines without a device frontier)."""
    if not eng.device_frontier:
        return None
    import jax
    import jax.numpy as jnp

    from repro.core.engine import _frontier_step

    nets = eng.frontier_networks(eng.prepare_many(csps))
    n, d = csps[0].dom.shape
    sds = jax.ShapeDtypeStruct
    idx = sds((rows,), jnp.int32)
    lowered = _frontier_step.lower(
        sds((4 * rows, n, d), jnp.bool_), sds((4 * rows, n), jnp.bool_), nets,
        idx, idx, idx, idx, idx, fix=eng.frontier_fix(),
    )
    return "tpu_custom_call" in lowered.compile().as_text()


def one_chip_phases(seed: int, budget: int, count: int = 16, n: int = 50,
                    alpha: float = 0.8, engines=ENGINES):
    """Yield one record per (engine, phase) over the seeded workload."""
    csps = workload(seed, count=count, n=n, alpha=alpha)
    closures, searches = ac3_oracle(csps, budget)
    for name in engines:
        yield phase_closures(name, csps, closures)
        yield phase_solve_many(name, csps, searches, budget)
        yield phase_service(name, csps, searches, budget)


def chip_checks(rec: dict) -> bool:
    """What only a chip run can hold a phase to: compiled Pallas kernels."""
    if rec["engine"].startswith("pallas"):
        if rec["phase"] == "closures" and rec["interpret"] is not False:
            return False
        if rec["phase"] == "solve_many" and rec["frontier_custom_call"] is not True:
            return False
    return True


# ---------------------------------------------------------------------------
# Four chips: the sharded enforcer
# ---------------------------------------------------------------------------


def perturbed_domains(csp, batch: int, seed: int):
    """``batch`` search-node domains: each assigns one random variable."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n, d = csp.dom.shape
    doms = np.tile(np.asarray(csp.dom)[None], (batch, 1, 1))
    for i in range(batch):
        var, val = rng.integers(n), rng.integers(d)
        doms[i, var, :] = False
        doms[i, var, val] = True
    return doms


def sharded_phase(seed: int, n: int = 1024, alpha: float = 0.5, batch: int = 8):
    """Yield one record per sharded impl, compared with one-chip einsum."""
    import jax
    import numpy as np

    from repro.engines import get_engine
    from repro.problems import generate

    csp = generate("model_rb", seed=seed, n=n, alpha=alpha, hardness=1.0)
    doms = perturbed_domains(csp, batch, seed)
    ref = jax.device_get(get_engine("einsum").prepare(csp).enforce_batch(doms))
    for impl in ("einsum", "bitpacked"):
        eng = get_engine("sharded", impl=impl)
        prepared = eng.prepare(csp)
        shards = {s.device for s in prepared.payload[1].addressable_shards}

        def run():
            return jax.block_until_ready(prepared.enforce_batch(doms))

        first, wall, res = _twice(run)
        res = jax.device_get(res)
        ok = np.asarray(ref.consistent)
        agree = (
            np.array_equal(np.asarray(res.consistent), ok)
            and np.array_equal(np.asarray(res.n_recurrences), np.asarray(ref.n_recurrences))
            and np.array_equal(np.asarray(res.dom)[ok], np.asarray(ref.dom)[ok])
        )
        rec = _record(f"sharded/{impl}", "sharded", [csp], first, wall,
                      agree and len(shards) == len(jax.devices()),
                      n=n, d=int(csp.dom.shape[1]), batch=batch,
                      shard_devices=len(shards), consistent=int(ok.sum()))
        yield rec


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget", type=int, default=200, help="assignments per search")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, over 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": args.chips}
    if device["platform"] != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {device['platform']} device(s)", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}))
        return 1

    from repro.launch import compile_cache

    compile_cache.enable()
    ok = True
    try:
        if args.chips == 4:
            records = sharded_phase(args.seed)
        else:
            records = one_chip_phases(args.seed, args.budget)
        for rec in records:
            ok &= rec["agree"] and chip_checks(rec)
            print(json.dumps(rec), flush=True)
    except Exception:
        traceback.print_exc()
        ok = False
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
