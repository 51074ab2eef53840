from benchmarks.chip.readers import rows_per_round as read  # noqa: F401
