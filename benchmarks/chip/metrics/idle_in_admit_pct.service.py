from benchmarks.chip.span_readers import idle_in_admit_pct as read  # noqa: F401
