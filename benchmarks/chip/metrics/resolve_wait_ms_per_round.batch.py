from benchmarks.chip.span_readers import resolve_wait_ms_per_round as read  # noqa: F401
