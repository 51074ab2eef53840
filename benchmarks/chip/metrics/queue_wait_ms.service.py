from benchmarks.chip.readers import queue_wait_ms as read  # noqa: F401
