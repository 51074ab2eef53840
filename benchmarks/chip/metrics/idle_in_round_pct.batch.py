from benchmarks.chip.span_readers import idle_in_round_pct as read  # noqa: F401
