from benchmarks.chip.span_readers import admit_ms_per_round as read  # noqa: F401
