from benchmarks.chip.readers import compiles_in_window as read  # noqa: F401
