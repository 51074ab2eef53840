from benchmarks.chip.readers import kernel_ms_per_round as read  # noqa: F401
