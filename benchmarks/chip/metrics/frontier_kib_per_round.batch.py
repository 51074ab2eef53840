from benchmarks.chip.readers import frontier_kib_per_round as read  # noqa: F401
