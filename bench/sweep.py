#!/usr/bin/env python3
"""Find the highest arrival rate a cell's engine sustains (the knee), once,
when the cell is defined; the cell then runs at a fixed rate written into
its traffic file.

The knee is the saturated rate: every slot kept busy by a backlog of the
mix's own requests (whole blocks, so the lengths are the mix's), measured
as output tokens per second over a long window, divided by the mix's mean
output tokens per request. Then each ``--fractions`` of that rate runs open
loop on a fresh engine for the same window, and reports whether the queue
of waiting requests grew.

    python3 bench/sweep.py --workload ds67b.chat_shared --seconds 150 \\
        --fractions 0.8 1.0 --seed 3

Prints one JSON line for the saturated run and one per fraction.
"""
import argparse
import gc
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
    ap.add_argument("--fractions", type=float, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np
    from bench.cell import load_cell
    from bench.device import NoChip, log, require_chips
    from bench.e2e import end_to_end
    from bench.generator import generate, lognormal_quantiles
    from bench.harness import build, make_engine, warm_up
    from bench.serve_loop import Client
    cell = load_cell(args.workload)
    import jax
    try:
        require_chips(cell.chips)
    except (NoChip, ValueError) as e:
        log(f"refused: {e}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    model, params = build(cell, args.seed)
    vocab, slots = cell.config["vocab_size"], cell.config["serving"]["slots"]
    block = int(cell.mix["block"])
    mean_out = float(lognormal_quantiles(cell.mix["output"], block).mean())
    lead = float(cell.mix["lead_in_s"])

    def measure(mix, lead_s):
        engine = make_engine(model, params, cell, args.seed)
        warm_up(engine, cell, vocab, np.random.default_rng([args.seed, 1]))
        arrivals = generate(mix, args.seed, vocab, slots)
        t_base = time.perf_counter()
        client = Client(engine, arrivals, t_base)
        client.run(t_base + lead_s)
        t0, q0 = time.perf_counter(), len(engine.pending_rids)
        client.run(t0 + args.seconds)
        t1, q1 = time.perf_counter(), len(engine.pending_rids)
        e = end_to_end(client.batches, client.due, client.first, t0, t1)
        steps = [c for c in client.calls_in(t0, t1) if c.kind == "step"]
        occ = (sum(c.live for c in steps) / (len(steps) * slots)
               if steps else 0.0)
        out = {"queue_start": q0, "queue_end": q1,
               "requests": len(arrivals), "slot_occupancy": occ,
               "window_s": t1 - t0, **e}
        del engine, client
        gc.collect()
        return out

    # whole blocks enough that the backlog outlasts the lead-in and window
    per_slot = -(-8 * block // slots)
    sat = measure(dict(cell.mix, arrivals={"process": "backlog",
                                           "per_slot": per_slot}), lead)
    knee = sat["output_tok_per_s"] / mean_out
    print(json.dumps({"saturated": sat, "mean_output_tokens": mean_out,
                      "knee_rate_per_s": knee}), flush=True)
    for f in args.fractions:
        arr = dict(cell.mix["arrivals"], rate_per_s=f * knee)
        mix = dict(cell.mix, arrivals=arr, horizon_s=lead + args.seconds)
        print(json.dumps({"fraction": f, "rate_per_s": f * knee,
                          **measure(mix, lead)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
