from benchmarks.chip.readers import cache_hit_pct as read  # noqa: F401
