from benchmarks.chip.readers import device_idle_pct as read  # noqa: F401
