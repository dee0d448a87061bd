#!/usr/bin/env python3
"""Readings that set a cell's limit on the logit gap: the system's gap and
the float8 control's, on the same served tokens, for several seeds in one
process (the benchmark's own runs never run the control). Both are judged
by the cell's limit, as a run judges its own: the control has to come out
not correct.

    python3 bench/calibrate.py --workload yi9b.decode_batch --seconds 51 \\
        --seeds 11 12 13

Prints one JSON line per seed: {"seed", "correct", "gap", "control_correct",
"control_gap", "limit", "metrics"}.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.cell import load_cell
    from bench.device import CompileClock, NoChip, log, require_chips
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
    clock = CompileClock()
    for seed in args.seeds:
        res = run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                       devices=devices, peaks=peaks, clock=clock,
                       t_process=time.perf_counter(), cache_dir=cache_dir,
                       control=True)
        gap, ctl = res["check"]["max_logit_gap"], res["control"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "gap": gap["value"],
                          "control_correct": ctl["correct"],
                          "control_gap": ctl["max_logit_gap"]["value"],
                          "limit": gap["limit"], "metrics": res["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
