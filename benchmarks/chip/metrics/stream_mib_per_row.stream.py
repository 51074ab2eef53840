from benchmarks.chip.stream_bytes import mib_per_row as read  # noqa: F401
