from benchmarks.chip.readers import round_ms as read  # noqa: F401
