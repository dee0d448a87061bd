#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload yi9b.decode_batch --seed 7 \\
        --seconds 51 --trace 0

A run builds the configuration's model through the system's own entry
(``launch/serve.py::build_served_model``, kernels on, weights made on the
device from ``--seed``), warms every shape its traffic can use, starts the
traffic (a lead-in, so the window opens on a loaded engine), and measures
for ``--seconds`` from the client's side. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` reports its per-layer metrics, with a
profiler trace of a few decode chunks after the window. Then it frees the
system and checks a sample of the served tokens against the plain
reference. The last line of standard output is one JSON object; the lines
on standard error before it say where the time went.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.device import CompileClock, NoChip, log, require_chips  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench.cell import load_cell
    cell = load_cell(args.workload)
    import jax
    try:
        devices, peaks = require_chips(cell.chips)
    except (NoChip, ValueError) as e:
        log(f"refused: {e}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.harness import run_cell
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, peaks=peaks,
                      clock=CompileClock(), t_process=T_PROCESS,
                      cache_dir=cache_dir)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
