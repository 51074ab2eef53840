from benchmarks.chip.readers import kernel_roofline_pct as read  # noqa: F401
