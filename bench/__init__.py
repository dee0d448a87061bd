"""The chip benchmark of this repository (BENCHMARK.json at the root).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Layout, all found by name:

- ``configs/<config>.json``: a model configuration as it is run: its
  ``family`` (dense when absent) and published keys, which the table in
  ``cell.py`` maps to the system's ``ModelConfig`` (a key it cannot place
  is refused; ``"published"`` holds published keys no field holds yet,
  ``"system"`` fields no published key names), weight and KV formats and
  engine options (``serving``, with ``tp`` the chips a model is sharded
  over), the reference it is checked against, and the limit of that check;
- ``traffic/<mix>.json``: a traffic mix, read by ``generator.py``;
- ``metrics/<metric>.py``: the reader of one per-layer metric;
- ``work/<kernel>.py``: FLOPs and bytes of one kernel's calls;
- ``references/<reference>.py``: a plain float32 reference forward;
- ``peaks.py``: the chips' published peaks;
- ``harness.py``, ``serve_loop.py``, ``e2e.py``, ``trace.py``: one run,
  the client loop, the end-to-end arithmetic, the trace reduction;
- ``calibrate.py`` and ``sweep.py``: the readings behind a check's limit
  and a cell's arrival rate, run once when a cell is defined;
- its CPU tests are ``tests/bench_harness/`` at the repository's root
  (``PYTHONPATH=src python -m pytest -q tests/bench_harness``).
"""
