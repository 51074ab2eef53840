from benchmarks.chip.span_readers import install_ms as read  # noqa: F401
