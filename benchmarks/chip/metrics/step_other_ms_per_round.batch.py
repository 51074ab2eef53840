from benchmarks.chip.readers import step_other_ms_per_round as read  # noqa: F401
