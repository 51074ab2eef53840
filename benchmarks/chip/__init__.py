"""The chip benchmark: cells of `BENCHMARK.json`, run one process each.

    python -m benchmarks.chip --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell pairs a configuration (``configs/<config>.json``) with a traffic mix
(``traffic/<mix>.json``); each per-layer metric is read by
``metrics/<metric>.py``. All are found by the names in `BENCHMARK.json`, so
a new cell, mix, configuration or metric is a new file and a new entry.
"""
