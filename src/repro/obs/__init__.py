"""`repro.obs` — structured observability: spans, metrics, Perfetto export.

One import surface for the three pieces (DESIGN.md §10):

- **tracer** (`obs.span` / `obs.fence`, `obs.tracing`): nested wall-clock
  spans (``service.request`` → ``driver.round`` → ``frontier.step`` →
  ``kernel.launch``) in a bounded ring. OFF by default — zero overhead —
  enabled by `enable()` or ``REPRO_TRACE=1``; ``timing="fenced"``
  (``REPRO_TRACE_TIMING=fenced``) opts into `jax.block_until_ready`
  fencing so spans measure device completion instead of async launch.
  While on, each span also lands in any `jax.profiler` trace taken
  meanwhile (a `TraceAnnotation` of its name), beside the device's ops;
  ``SPANS`` declares every name the program opens.
- **registry** (`obs.REGISTRY`, `obs.counter_add` / `gauge_set` /
  `observe`): always-on named counters/gauges/histograms every subsystem
  publishes into; `snapshot()` is the one ``repro-obs/v1`` dict the
  benchmarks and tracker consume.
- **export** (`obs.dump_run` / `write_trace`, ``python -m repro.obs``):
  run dumps and Chrome-trace/Perfetto timelines.

This package imports only the standard library + numpy (jax is deferred
inside `enable` and `fence`), so instrumented core modules can import it
without cycles or import-time cost.
"""

from . import export, registry, tracing  # noqa: F401  (submodule access)
from .export import child_coverage, chrome_trace, dump_run, load_run, run_payload, write_trace
from .registry import (
    REGISTRY,
    SCHEMA,
    Registry,
    RegistryScope,
    counter_add,
    gauge_set,
    mean,
    observe,
    percentile,
    snapshot,
    summarize,
)
from .tracing import (
    SPANS,
    Span,
    Tracer,
    disable,
    enable,
    enable_from_env,
    enabled,
    fence,
    get_tracer,
    now,
    record_complete,
    span,
)

__all__ = [
    "REGISTRY", "SCHEMA", "SPANS", "Registry", "RegistryScope", "Span", "Tracer",
    "child_coverage", "chrome_trace", "counter_add", "disable", "dump_run",
    "enable", "enable_from_env", "enabled", "fence", "gauge_set",
    "get_tracer", "load_run", "mean", "now", "observe", "percentile",
    "record_complete", "run_payload", "snapshot", "span", "summarize",
    "write_trace",
]

# honour REPRO_TRACE=1 at first import, wherever that import happens
enable_from_env()
