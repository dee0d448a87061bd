"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table5,...] \
        [--quant pofx5es1,fxp6]

Each bench returns (rows, claims). Rows land in experiments/bench/*.csv;
the claims dict is printed as ``bench,claim,value`` lines — EXPERIMENTS.md
§Claims is generated from this output. ``--quant`` (the shared policy/spec
grammar, see repro.core.policy) appends extra comma-separated spec strings
to every format-sweeping bench that accepts them.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

BENCHES = [
    ("fig1_quant_error", "benchmarks.bench_fig1_quant_error"),
    ("fig2_tradeoff", "benchmarks.bench_fig2_tradeoff"),
    ("table2_normposit", "benchmarks.bench_table2_normposit"),
    ("fig10_pofx", "benchmarks.bench_fig10_pofx"),
    ("table3_pareto", "benchmarks.bench_table3_pareto"),
    ("table5_accuracy", "benchmarks.bench_table5_accuracy"),
    ("table6_joint", "benchmarks.bench_table6_joint"),
    ("fig20_accel", "benchmarks.bench_fig20_accel"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
    ("serve_engine", "benchmarks.bench_serve_engine"),
]


def main(argv=None) -> int:
    from repro.core.policy import add_policy_arg, parse_spec
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench name substrings")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny inputs for every bench that supports them — "
                         "a bit-rot check (tests/test_bench_smoke.py runs "
                         "this per bench in CI), not a measurement")
    add_policy_arg(ap, default="",
                   extra_help="extra spec strings appended to the "
                              "format-sweeping benches")
    args = ap.parse_args(argv)
    enable_compile_cache()
    only = [s for s in args.only.split(",") if s]
    extra_specs = tuple(s for s in args.quant.split(",") if s)
    for s in extra_specs:
        if parse_spec(s) is None:  # fail fast on typos / the keep sentinel
            raise SystemExit(f"--quant: {s!r} is not a quantized format")
    failures = []
    for name, module in BENCHES:
        if only and not any(s in name for s in only):
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            kwargs = {}
            sig = inspect.signature(mod.run).parameters
            if "extra_specs" in sig:
                kwargs["extra_specs"] = extra_specs
            if args.smoke and "smoke" in sig:
                kwargs["smoke"] = True
            rows, claims = mod.run(**kwargs)
            dt = time.time() - t0
            print(f"=== {name}: {len(rows)} rows in {dt:.1f}s")
            for k, v in claims.items():
                print(f"{name},{k},{v}")
        except Exception:
            failures.append(name)
            print(f"=== {name}: FAILED")
            traceback.print_exc()
    if failures:
        print(f"FAILED benches: {failures}")
        return 1
    print("all benches ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
