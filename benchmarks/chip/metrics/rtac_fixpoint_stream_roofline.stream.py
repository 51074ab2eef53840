from benchmarks.chip import stream_bytes


def read(record):
    return stream_bytes.roofline_pct(record, stream_bytes.config_dims("rb600_dense_batch"))
