"""Run one cell of `BENCHMARK.json` on the chip and print its result line.

    python -m benchmarks.chip --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip. Set-up generates the cell's traffic from the seed and
warms up every shape the window uses; the window then drives the system for
``--seconds``; afterwards what the window answered is held to the plain
reference (`reference`). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number compared
with its limit, which are also the last lines of standard error.

Two entries, chosen by the configuration's ``entry``:

- ``service``: an open loop on the wall clock into `SolverService`. Each
  request is timed from when it was due, so a stall counts against every
  later request; one that does not finish counts as infinitely late.
- ``solve_many``: a closed loop of back-to-back `solve_many` batches of fresh
  instances, built by a producer thread while the previous batch runs. The
  window is whole batches: it ends with the batch that crosses ``--seconds``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import queue
import shutil
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import cells, reference, stats, trace as trace_mod, traffic
from .kernel_bytes import fixpoint_row_bytes

KERNEL = "rtac_fixpoint_{encoding}"
ENCODING = {"pallas_packed": "packed"}
#: how long past the window's close an open loop waits for its last answers
DRAIN_S = 60.0
#: seconds of the window a traced run profiles (see `Window`)
TRACE_S = 6.0
#: open-loop warm-up (`warm_service`): requests installed at a time while the
#: cache fills, searches sent at once, and the cell's own traffic from the
#: warm-up seed stream at this multiple of its rate for this many seconds
FILL_GROUP = 16
WARM_BURST = 96
WARM_RATE_SCALE = 1.25
WARM_SECONDS = 4.0
#: closed-loop warm-up: batches of the cell's size from the warm-up stream
WARM_BATCHES = 2
#: JSON cannot hold an infinite latency; a tail that reaches a request that
#: never finished reads as the largest double
NEVER = sys.float_info.max


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, started: Optional[float] = None) -> int:
    started = time.monotonic() if started is None else started
    args = parse(argv)
    cell = cells.load(args.workload)
    import jax

    found = jax.devices()
    if found[0].platform != "tpu" or len(found) < cell.chips:
        print(f"{args.workload}: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(found)} {found[0].platform} device(s)", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), started)
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Plumbing shared by both entries
# ---------------------------------------------------------------------------


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, since `reset`
    (JAX times both under one backend-compile event), with their names."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names: List[str] = []

    @property
    def count(self) -> int:
        return len(self.names)

    def _on_duration(self, event: str, _secs: float, fun_name: str = "?", **_kw) -> None:
        if event == self.EVENT:
            self.names.append(fun_name)

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def reset(self) -> None:
        self.names = []


def trace_dir(workload: str) -> str:
    """Fixed per cell, inside the checkout; emptied before each traced run."""
    return os.path.join(str(cells.ROOT), ".bench_trace", workload)


class Window:
    """The measured part of a run: registry deltas, compiles and, when
    traced, the profiler trace and the program's spans.

    A traced run profiles only the window's first `TRACE_S` seconds (to the
    end of the batch that crosses them): the profiler and the span tracer
    slow the host enough that a longer trace pushes an open loop past its
    knee. The per-layer counters, spans and compile count are those of the
    traced part."""

    def __init__(self, traced: bool, workload: str, compiles: CompileCounter):
        self.traced = traced
        self.dir = trace_dir(workload)
        self.compiles = compiles
        self.spans: Dict[str, List[float]] = {}
        self.tracing = False
        self.traced_until: Optional[float] = None

    def annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "Window":
        import jax
        from repro import obs

        self.scope = obs.REGISTRY.scope().__enter__()
        if self.traced:
            shutil.rmtree(self.dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            obs.enable(capacity=1 << 21)
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._mark = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            self._mark.__enter__()
            self.tracing = True
        self.compiles.reset()
        self.opened = time.monotonic()
        return self

    def tick(self) -> None:
        """Stop the trace once `TRACE_S` of the window have passed."""
        if self.tracing and time.monotonic() - self.opened >= TRACE_S:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax
        from repro import obs

        self.traced_until = time.monotonic()
        self.counters = self.scope.counters()
        self.compiled = self.compiles.count
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        for span in obs.disable().spans:
            self.spans.setdefault(span.name, []).append(span.dur)
        self.tracing = False

    def __exit__(self, *exc) -> None:
        if self.compiles.names:
            print(f"compiled in the window: {', '.join(self.compiles.names)}", file=sys.stderr)
        if self.tracing:
            self._stop_trace()
        elif not self.traced:
            self.counters = self.scope.counters()
            self.compiled = self.compiles.count


def read_trace(window: Window, kernel: str) -> dict:
    from jax.profiler import ProfileData

    t0 = time.monotonic()
    path = trace_mod.find_xplane(window.dir)
    reduced = trace_mod.reduce(ProfileData.from_file(path), [kernel])
    print(f"trace of {os.path.getsize(path)} B reduced in {time.monotonic() - t0:.1f} s",
          file=sys.stderr)
    return reduced


def memory_peak() -> Optional[int]:
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def to_csp(inst: traffic.Instance):
    from repro.core.csp import CSP

    return CSP(cons=inst.cons, mask=inst.mask, dom=inst.dom)


def answer_of(solution, st) -> reference.Answer:
    return reference.Answer(solution, st.n_assignments, st.n_backtracks, bool(st.exhausted))


def check_lines(checks: Dict[str, dict]) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)


def finish(cell, metrics: Dict[str, float], *, attempted: int,
           failed: int, checks: Dict[str, dict], peak, window: Window,
           record: dict) -> dict:
    """Assemble the result line (end-to-end metrics, or per-layer ones when
    traced) and print the compared numbers last on standard error."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": attempted, "failed": failed}
    if window.traced:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        layer = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](record)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = layer
        out["device"] = device
        out["breakdown"] = {"device_ops": trace_mod.top(tr["ops_s"]),
                            "idle_gaps": trace_mod.top(tr["idle_s"])}
    else:
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    check_lines(checks)
    return out


def run(cell: cells.Cell, seed: int, seconds: float, traced: bool, started: float) -> dict:
    """One run of ``cell`` with the persistent compilation cache on."""
    import jax
    from repro.launch import compile_cache

    compile_cache.enable()
    # every program, however quick to compile, is kept for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return drive(cell, seed, seconds, traced, started)


def drive(cell: cells.Cell, seed: int, seconds: float, traced: bool, started: float) -> dict:
    """Set up, measure and check one run of ``cell`` on this process's device."""
    entries = {"service": run_service, "solve_many": run_batches}
    entry = cell.config["entry"]
    if entry not in entries:
        raise ValueError(f"unknown entry {entry!r}; expected one of {sorted(entries)}")
    prob = cell.config["problem"]
    d, _ = traffic.model_rb_shape(prob["n"], prob["alpha"], prob["r"])
    if d != prob["d"]:
        raise ValueError(f"{cell.name}: n={prob['n']}, alpha={prob['alpha']} give d={d}, "
                         f"not the stated d={prob['d']}")
    with CompileCounter() as compiles:
        return entries[entry](cell, seed, seconds, traced, started, compiles)


def _instances(cfg: dict, arrivals, built: Dict[tuple, object]) -> None:
    """Build each arrival's instance once (a pool instance may recur)."""
    for a in arrivals:
        if a.instance not in built:
            built[a.instance] = to_csp(traffic.instance(cfg["problem"], a.instance))


# ---------------------------------------------------------------------------
# Open loop into SolverService
# ---------------------------------------------------------------------------


def drive_open_loop(svc, arrivals, built, budget: int, annotate: Callable,
                    tick: Callable = lambda: None, drain_s: float = DRAIN_S):
    """Submit every arrival at its due time and step the service until all
    are answered (or ``drain_s`` past the last is up). Single-threaded: a
    long step delays the submissions behind it, and their latency shows it.
    Returns (t0, requests, seconds each submission ran late)."""
    n = len(arrivals)
    reqs: list = [None] * n
    late = np.zeros(n)
    t0 = time.monotonic()
    give_up = t0 + arrivals[-1].due + drain_s
    i = 0
    while True:
        tick()
        now = time.monotonic()
        while i < n and t0 + arrivals[i].due <= now:
            a = arrivals[i]
            with annotate("submit"):
                reqs[i] = svc.submit(built[a.instance], max_assignments=budget)
            late[i] = time.monotonic() - (t0 + a.due)
            i += 1
        if svc.has_work:
            if now > give_up:
                break
            with annotate("SolverService.step"):
                svc.step()
        elif i < n:
            wait = t0 + arrivals[i].due - time.monotonic()
            if wait > 0:
                with annotate("arrival.wait"):
                    time.sleep(wait)
        else:
            break
    return t0, reqs, late


def service_bucket(cfg: dict):
    """The admission bucket the service pads this configuration's instances to."""
    from repro.service.buckets import bucket_for

    return bucket_for(cfg["problem"]["n"], cfg["problem"]["d"])


def warm_service(svc, cfg: dict, mix: dict, seed: int, budget: int) -> None:
    """Fill the prepared-network cache, send a burst, then run the cell's
    traffic from the warm-up seed stream, so the slot pool is at the size
    the window needs and the round widths it reaches are compiled before it
    opens. (The frontier table's size is the configuration's: the service's
    ``initial_slots`` presize it.)

    The fill installs one network per request, enough requests to fill the
    cache's budget, in groups of `FILL_GROUP` requests stopped after one
    assignment; for a pooled mix they are the pool's most popular instances."""
    bucket = service_bucket(cfg)
    fill = svc.cache.byte_budget // svc.engine.network_nbytes(bucket.n_p, bucket.d_p) + 1
    pool = mix.get("pool")
    if pool is None:
        seeds = [(seed, traffic.WARMUP, 0, i) for i in range(fill)]
    else:
        seeds = [(seed, traffic.POOL, k) for k in range(min(fill, int(pool["size"])))]
    built: Dict[tuple, object] = {}
    for start in range(0, len(seeds), FILL_GROUP):
        arrivals = [traffic.Arrival(0.0, s) for s in seeds[start:start + FILL_GROUP]]
        _instances(cfg, arrivals, built)
        for a in arrivals:
            svc.submit(built[a.instance], max_assignments=1)
        svc.run_until_idle()
    built.clear()
    # a burst of searches at once, so the widest rounds the window may
    # reach are compiled before it opens
    burst = traffic.open_loop(seed, (traffic.WARMUP, 2), dict(mix, rate_rps=WARM_BURST), 1.0)
    _instances(cfg, burst, built)
    for a in burst:
        svc.submit(built[a.instance], max_assignments=budget)
    svc.run_until_idle()
    built.clear()
    scaled = dict(mix, rate_rps=float(mix["rate_rps"]) * WARM_RATE_SCALE)
    arrivals = traffic.open_loop(seed, (traffic.WARMUP, 1), scaled, WARM_SECONDS)
    _instances(cfg, arrivals, built)
    drive_open_loop(svc, arrivals, built, budget, lambda _n: contextlib.nullcontext())
    # the frontier may have grown while the warm-up drained: admit one more
    # search so the root upload at its final size is compiled too
    svc.submit(built[arrivals[0].instance], max_assignments=1)
    svc.run_until_idle()
    rows = [b.get("frontier_rows") for b in svc.snapshot()["buckets"].values()]
    print(f"warm-up done: frontier rows {rows}", file=sys.stderr)


def service_metrics(t0: float, arrivals, reqs, seconds: float):
    """End-to-end metrics of an open-loop window, and which requests were
    answered. A latency runs from the request's due time; a request that was
    not answered (failed, shed, timed out, never finished) is infinitely
    late, and a percentile that reaches it reads `NEVER`."""
    from repro.service.service import RequestStatus

    done = [r is not None and r.status is RequestStatus.DONE for r in reqs]
    latency_ms = [
        1e3 * (r.finished_at - (t0 + a.due)) if ok else math.inf
        for r, ok, a in zip(reqs, done, arrivals)
    ]
    served = sum(ok and r.finished_at - t0 <= seconds for r, ok in zip(reqs, done))
    metrics = {
        "p50_latency_ms": stats.percentile(latency_ms, 50),
        "p95_latency_ms": stats.percentile(latency_ms, 95),
        "served_rps": served / seconds,
    }
    return {k: (NEVER if math.isinf(v) else v) for k, v in metrics.items()}, done


def run_service(cell, seed: int, seconds: float, traced: bool, started: float,
                compiles: CompileCounter) -> dict:
    from repro.service import SolverService

    cfg, mix = cell.config, cell.traffic
    budget = int(cfg["search"]["max_assignments"])
    svc = SolverService(engine=cfg["engine"], **cfg.get("service", {}))
    warm_service(svc, cfg, mix, seed, budget)
    arrivals = traffic.open_loop(seed, (traffic.WINDOW,), mix, seconds)
    built: Dict[tuple, object] = {}
    _instances(cfg, arrivals, built)
    setup_s = time.monotonic() - started

    with Window(traced, cell.name, compiles) as window:
        t0, reqs, late = drive_open_loop(svc, arrivals, built, budget, window.annotate,
                                         window.tick)
    peak = memory_peak()

    metrics, done = service_metrics(t0, arrivals, reqs, seconds)
    metrics["setup_s"] = setup_s
    due = [t0 + a.due for a in arrivals]
    rows = [b.get("frontier_rows") for b in svc.snapshot()["buckets"].values()]
    print(f"{cell.name}: {len(reqs)} requests due in {seconds} s; generator late by "
          f"p50 {1e3 * np.median(late):.3f} ms, max {1e3 * late.max():.3f} ms; "
          f"{window.compiled} compiles in the {'traced part of the ' * traced}window; "
          f"frontier rows {rows}", file=sys.stderr)

    record = {"counters": window.counters, "spans": window.spans,
              "compiles_in_window": window.compiled,
              "queue_wait_s": [r.admitted_at - t for r, t in zip(reqs, due)
                               if r is not None and r.admitted_at is not None
                               and t < (window.traced_until or math.inf)]}
    if traced:
        bucket = service_bucket(cfg)
        enc = ENCODING[cfg["engine"]]
        record["trace"] = read_trace(window, KERNEL.format(encoding=enc))
        record["kernel"] = KERNEL.format(encoding=enc)
        record["kernel_row_bytes"] = fixpoint_row_bytes(enc, bucket.n_p, bucket.d_p)
        record["hbm_bytes_per_s"] = stats.peaks(_device_kind())["hbm_bytes_per_s"]

    # the answers, once the window has closed and the peak is read
    answers = [answer_of(r.solution, r.stats) if ok else None for r, ok in zip(reqs, done)]
    sample = _sample(seed, len(arrivals), int(cfg["check"]["sample"]))
    checks = compare([built[a.instance] for a in arrivals], answers, budget, sample)
    print(f"{cell.name}: {len(sample)} of {len(arrivals)} answers held to the reference",
          file=sys.stderr)
    return finish(cell, metrics, attempted=len(reqs), failed=len(reqs) - sum(done),
                  checks=checks, peak=peak, window=window, record=record)


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _sample(seed: int, count: int, size: int) -> List[int]:
    rng = np.random.default_rng([seed, traffic.CHECK])
    return sorted(rng.choice(count, size=min(size, count), replace=False).tolist())


def compare(csps, answers: List[Optional[reference.Answer]], budget: int,
            sample: List[int]) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit: requests
    never answered; answered solutions that break a constraint; and, over a
    sample drawn from the seed, answers that differ from the reference's
    (solution, assignments, backtracks, budget stop)."""
    invalid = sum(
        a is not None and a.solution is not None
        and not reference.satisfies(c.cons, c.mask, c.dom, a.solution)
        for c, a in zip(csps, answers)
    )
    mismatched = mismatches([(csps[i], answers[i]) for i in sample], budget)
    return {
        "unanswered": {"value": sum(a is None for a in answers), "limit": 0},
        "invalid_solutions": {"value": int(invalid), "limit": 0},
        "mismatched": {"value": mismatched, "limit": 0},
    }


def mismatches(pairs, budget: int) -> int:
    """How many answered (instance, answer) pairs differ from the reference."""
    return sum(
        answer is not None and answer != reference.solve(c.cons, c.mask, c.dom, budget)
        for c, answer in pairs
    )


# ---------------------------------------------------------------------------
# Closed loop of solve_many batches
# ---------------------------------------------------------------------------


class Producer:
    """Builds batch after batch of host instances on a thread of its own, one
    batch ahead of the caller."""

    def __init__(self, cfg: dict, seed: int, stream: int, size: int):
        self._cfg, self._seed, self._stream, self._size = cfg, seed, stream, size
        self._queue: "queue.Queue" = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        index = 0
        while not self._stop.is_set():
            batch = build_batch(self._cfg, self._seed, self._stream, index, self._size)
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            index += 1

    def get(self):
        return self._queue.get()

    def close(self) -> None:
        self._stop.set()
        with contextlib.suppress(queue.Empty):
            self._queue.get_nowait()
        self._thread.join(timeout=60)


def closed_loop(next_batch: Callable, solve: Callable, settle: Callable,
                seconds: float, annotate: Callable, tick: Callable = lambda: None):
    """Back-to-back batches until one ends ``seconds`` or more after the
    first began: the window is whole batches. Each batch is timed alone;
    ``settle`` checks its answers off the clock. Returns each batch's
    seconds and how long each wait for the producer took."""
    durations: List[float] = []
    waits: List[float] = []
    t_open = None
    while True:
        w0 = time.monotonic()
        with annotate("producer.wait"):
            batch = next_batch()
        waits.append(time.monotonic() - w0)
        b0 = time.monotonic()
        t_open = b0 if t_open is None else t_open
        with annotate("solve_many"):
            result = solve(batch)
        b1 = time.monotonic()
        durations.append(b1 - b0)
        with annotate("check.solutions"):
            settle(batch, result)
        tick()
        if b1 - t_open >= seconds:
            return durations, waits


def run_batches(cell, seed: int, seconds: float, traced: bool, started: float,
                compiles: CompileCounter) -> dict:
    from repro.core.search import solve_many
    from repro.engines import get_engine

    cfg, mix = cell.config, cell.traffic
    size, budget = int(mix["batch"]), int(cfg["search"]["max_assignments"])
    engine = get_engine(cfg["engine"])
    producer = Producer(cfg, seed, traffic.WINDOW, size)
    try:
        # the round widths a batch climbs through vary a little from batch
        # to batch: warm up on several, from the warm-up seed stream
        for index in range(WARM_BATCHES):
            warm = [to_csp(i) for i in build_batch(cfg, seed, traffic.WARMUP, index, size)]
            solve_many(warm, engine=engine, max_assignments=budget)
            del warm
        setup_s = time.monotonic() - started

        kept: List[tuple] = []  # (instance, answer) of the reservoir sample
        tally = {"attempted": 0, "invalid": 0, "unanswered": 0}
        rng = np.random.default_rng([seed, traffic.CHECK])
        keep = int(cfg["check"]["sample"])

        def solve(batch):
            return solve_many([to_csp(i) for i in batch], engine=engine,
                              max_assignments=budget)

        def settle(batch, result):
            """Off the clock: check every solution, keep a seeded sample; an
            instance the batch returned nothing for is unanswered."""
            sols, sts = (list(r) + [None] * (len(batch) - len(r)) for r in result)
            for inst, sol, st in zip(batch, sols, sts):
                answer = answer_of(sol, st) if st is not None else None
                tally["unanswered"] += answer is None
                tally["invalid"] += sol is not None and not reference.satisfies(
                    inst.cons, inst.mask, inst.dom, sol)
                # reservoir sample over every instance of the window
                seen = tally["attempted"]
                if len(kept) < keep:
                    kept.append((inst, answer))
                elif (j := int(rng.integers(seen + 1))) < keep:
                    kept[j] = (inst, answer)
                tally["attempted"] = seen + 1

        with Window(traced, cell.name, compiles) as window:
            durations, waits = closed_loop(producer.get, solve, settle, seconds,
                                           window.annotate, window.tick)
    finally:
        producer.close()
    peak = memory_peak()

    attempted, unanswered = tally["attempted"], tally["unanswered"]
    metrics = {"solve_rate": attempted / sum(durations), "setup_s": setup_s}
    print(f"{cell.name}: {len(durations)} batches of {size} in {sum(durations):.3f} s; "
          f"producer waits {sum(waits):.3f} s in all (max {max(waits):.3f} s); "
          f"{window.compiled} compiles in the {'traced part of the ' * traced}window", file=sys.stderr)
    record = {"counters": window.counters, "spans": window.spans,
              "compiles_in_window": window.compiled}
    if traced:
        from repro.kernels.ops import kernel_dims

        enc = ENCODING[cfg["engine"]]
        n_p, d_p = kernel_dims(enc, cfg["problem"]["n"], cfg["problem"]["d"])[:2]
        record["trace"] = read_trace(window, KERNEL.format(encoding=enc))
        record["kernel"] = KERNEL.format(encoding=enc)
        record["kernel_row_bytes"] = fixpoint_row_bytes(enc, n_p, d_p)
        record["hbm_bytes_per_s"] = stats.peaks(_device_kind())["hbm_bytes_per_s"]

    checks = {
        "unanswered": {"value": unanswered, "limit": 0},
        "invalid_solutions": {"value": tally["invalid"], "limit": 0},
        "mismatched": {"value": mismatches(kept, budget), "limit": 0},
    }
    print(f"{cell.name}: {len(kept)} of {attempted} answers held to the reference",
          file=sys.stderr)
    return finish(cell, metrics, attempted=attempted, failed=unanswered,
                  checks=checks, peak=peak, window=window, record=record)


def build_batch(cfg: dict, seed: int, stream: int, index: int, size: int):
    """The host instances of batch ``index`` of a seed stream."""
    return [traffic.instance(cfg["problem"], e)
            for e in traffic.batch_instances(seed, stream, index, size)]
