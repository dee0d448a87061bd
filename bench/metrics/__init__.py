"""One reader per per-layer metric, ``<metric>.py`` with ``read(record)``,
found by the metric's name in BENCHMARK.json. A reader that finds nothing
to read returns None, and the metric is left out of the result line."""
