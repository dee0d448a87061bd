"""One run of one cell: set-up, lead-in, the measured window, an optional
trace, and the check of the served tokens against the plain reference."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import shutil
import time
from typing import Optional

import numpy as np

from bench.cell import ROOT, Cell, metric_reader, model_config
from bench.device import log
from bench.e2e import end_to_end
from bench.generator import generate, prompt_lengths
from bench.serve_loop import Client

WARM_RID = 1 << 30      # warm-up requests' ids; the traffic's start at 0
TRACE_CHUNKS = 3        # decode chunks in the profiler trace
TRACE_DIR = ROOT / ".bench_trace"


@dataclasses.dataclass
class Record:
    """What a per-layer metric's reader reads."""
    config: dict
    n_slots: int
    peaks: object
    t0: float                  # the window, host clock
    t1: float
    client: Client
    calls: list                # the engine calls inside the window
    stats0: dict               # engine.stats() at the window's ends
    stats1: dict
    decode_time: float         # engine counters' change over the window
    decode_steps: int
    trace: Optional[object] = None    # bench.trace.Summary of the trace
    traced_calls: list = dataclasses.field(default_factory=list)


def build(cell: Cell, seed: int):
    """(model, params) of the cell's configuration, sharded over
    ``serving.tp`` chips (1 when the file does not say)."""
    import jax
    from repro.core.policy import QuantPolicy, parse_kv_spec
    from repro.launch.serve import build_served_model
    sv = cell.config["serving"]
    model, params = build_served_model(
        model_config(cell.config), QuantPolicy.from_string(sv["weights"]),
        parse_kv_spec(sv["kv_cache"]), use_kernel=bool(sv["kernels"]),
        tp=sv.get("tp", 1), seed=seed & 0xFFFFFFFF)
    jax.block_until_ready(params)
    return model, params


def make_engine(model, params, cell: Cell, seed: int):
    from repro.launch.engine import ServeEngine
    sv = cell.config["serving"]
    return ServeEngine(model, params, n_slots=sv["slots"],
                       max_len=sv["max_len"], chunk=sv["chunk"],
                       prompt_bucket=sv["prompt_bucket"],
                       seed=seed & 0x7FFFFFFF, paged=bool(sv["paged"]),
                       page_size=sv.get("page_size", 16))


def warm_lengths(lo: int, hi: int, bucket: int, max_len: int,
                 paged: bool) -> list:
    """Prompt lengths that reach every prefill program prompts in
    ``[lo, hi]`` can: one per length bucket, and in the dense engine a
    bucket-filling prompt too (it takes another program)."""
    out = set()
    groups = {}
    for p in range(lo, hi + 1):
        groups.setdefault(min(-(-p // bucket) * bucket, max_len), []).append(p)
    for pb, ps in groups.items():
        short = [p for p in ps if p != pb]
        if short:
            out.add(max(short))
        if pb in ps and (not paged or not short):
            out.add(pb)
    return sorted(out)


def warm_up(engine, cell: Cell, vocab: int, rng) -> int:
    """Run every program the cell's traffic can call, through the public
    API, so none compiles in the window. Returns the requests used."""
    from repro.launch.engine import Request
    rid = WARM_RID
    lo, hi = prompt_lengths(cell.mix)
    bucket, max_len = engine.prompt_bucket, engine.max_len

    def serve(prompt, max_new):
        nonlocal rid
        engine.submit(Request(rid=rid, prompt=prompt, max_new=max_new))
        rid += 1
        engine.admit_ready()
        while engine.active_rids:
            engine.step()

    tokens = lambda n: rng.integers(0, vocab, n, dtype=np.int32)
    lengths = warm_lengths(lo, hi, bucket, max_len, engine.paged)
    for p in lengths:
        serve(tokens(p), 1)
    shared = cell.mix["prompt"].get("shared")
    if engine.paged and shared:
        # every suffix bucket behind a resident shared prefix
        system = tokens(shared["tokens"])
        serve(np.concatenate([system, tokens(lengths[0] - len(system))]), 1)
        for p in lengths:
            serve(np.concatenate([system, tokens(p - len(system))]), 1)
    step = engine.chunk
    while step >= 1:           # every chunk length ``step`` can choose
        serve(tokens(lo), step + 1)
        step //= 2
    return rid - WARM_RID


def _fullest_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             peaks, clock, t_process: float, cache_dir: str,
             control: bool = False) -> dict:
    """One run; ``control`` (calibration only, never a benchmark run) also
    reads the float8 control's gap on the same served tokens and judges it
    by the same limit, under ``result["control"]``."""
    import jax
    sv = cell.config["serving"]
    log(f"cell {cell.name}: {cell.config['name']} x {cell.traffic_name}, "
        f"seed {seed}, {seconds} s window, trace {int(trace)}; "
        f"{devices[0].device_kind} x {len(devices)}; compile cache "
        f"{cache_dir}")

    t, c = time.perf_counter(), clock.secs
    model, params = build(cell, seed)
    params_s, params_c = time.perf_counter() - t, clock.secs - c
    t, c = time.perf_counter(), clock.secs
    engine = make_engine(model, params, cell, seed)
    rng = np.random.default_rng([seed, 1])
    n_warm = warm_up(engine, cell, cell.config["vocab_size"], rng)
    warm_s, warm_c = time.perf_counter() - t, clock.secs - c

    arrivals = generate(cell.mix, seed, cell.config["vocab_size"],
                        sv["slots"])
    t_base = time.perf_counter()
    client = Client(engine, arrivals, t_base)
    c = clock.secs
    client.run(t_base + float(cell.mix["lead_in_s"]))
    lead_s, lead_c = time.perf_counter() - t_base, clock.secs - c

    t0 = time.perf_counter()
    setup_s = t0 - t_process
    stats0, n_compiles0 = engine.stats(), clock.count
    dt0, ds0 = engine.decode_time, engine.decode_steps
    client.run(t0 + seconds)
    t1 = time.perf_counter()
    stats1, n_compiles = engine.stats(), clock.count - n_compiles0
    record = Record(config=cell.config, n_slots=sv["slots"], peaks=peaks,
                    t0=t0, t1=t1, client=client,
                    calls=client.calls_in(t0, t1), stats0=stats0,
                    stats1=stats1, decode_time=engine.decode_time - dt0,
                    decode_steps=engine.decode_steps - ds0)
    log(f"setup {setup_s:.3f} s: params {params_s:.3f} s ({params_c:.3f} s "
        f"compiling, {params_s - params_c:.3f} s device and host), warm-up "
        f"{warm_s:.3f} s ({warm_c:.3f} s compiling, {n_warm} requests), "
        f"lead-in {lead_s:.3f} s ({lead_c:.3f} s compiling)")
    log(f"window {t1 - t0:.3f} s: {len(record.calls)} engine calls, "
        f"{record.decode_steps} decode steps, {n_compiles} compilations "
        f"inside the window (must be 0)")

    breakdown = None
    if trace:
        record.trace, record.traced_calls, breakdown = _traced(
            client, cell)
    memory_peak = _fullest_peak(devices[:cell.chips])
    limit = (devices[0].memory_stats() or {}).get("bytes_limit", 0)
    log(f"peak device memory {memory_peak / 1e9:.3f} GB of "
        f"{limit / 1e9:.3f} GB")

    e2e = end_to_end(client.batches, client.due, client.first, t0, t1)
    late = [client.submitted_at[r] - client.due[r] for r in client.due
            if t0 <= client.due[r] <= t1]
    log(f"samples: {e2e['n_tpot']} requests with a time per output token, "
        f"{e2e['n_ttft']} due in the window ({e2e['n_ttft_missed']} with no "
        f"first token by its end); submissions late by at most "
        f"{max(late, default=0.0):.3f} s (the client submits between "
        f"engine calls)")
    metrics = {}
    if not trace:
        values = {"setup_s": setup_s, **e2e}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for name, m in metrics.items():
        log(f"metric {name} = {m['value']!r} {m['unit']}")

    attempted = sum(1 for r in client.due.values() if r <= t1)
    failed = 0
    # what was served, then free the system before the reference runs
    states = engine._states
    served = {rid: (np.asarray(states[rid].req.prompt),
                    np.asarray(states[rid].out, np.int32))
              for rid in client.due if states[rid].out}
    del states, engine, params, model, record
    client.engine = None
    gc.collect()
    check = check_served(cell, seed, served, control)
    correct = within_limit(check["value"], check["limit"]) and failed == 0
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": memory_peak},
    }
    if trace:
        result["device"]["busy_s"] = breakdown.pop("busy_s")
        result["device"]["window_s"] = breakdown.pop("window_s")
        result["breakdown"] = breakdown
    if control:
        result["control"] = {
            "correct": within_limit(check["control_gap"], check["limit"]),
            "max_logit_gap": {"value": check["control_gap"],
                              "limit": check["limit"]}}
        log(f"control max_logit_gap = {check['control_gap']!r} (limit "
            f"{check['limit']}): correct {result['control']['correct']}")
    result["check"] = {"max_logit_gap": {"value": check["value"],
                                         "limit": check["limit"]}}
    log(f"check: {check['n_requests']} requests, {check['n_tokens']} served "
        f"tokens against the reference in {check['seconds']:.1f} s")
    log(f"check max_logit_gap = {check['value']!r} (limit {check['limit']})")
    return result


def within_limit(value: float, limit: float) -> bool:
    """The comparison that decides ``correct``: the compared number, here
    the widest logit gap, may not pass its limit."""
    return value <= limit


def _traced(client: Client, cell: Cell):
    """Trace a few more decode chunks, as the traffic goes on."""
    import jax
    from jax.profiler import TraceAnnotation
    from bench import trace as tr
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    with TraceAnnotation("bench.traced"):
        tt0 = time.perf_counter()
        client.run(tt0 + 60.0, max_steps=TRACE_CHUNKS)
        tt1 = time.perf_counter()
    jax.profiler.stop_trace()
    summary = tr.summarize(tr.compact(tr.find_xplane(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    calls = client.calls_in(tt0, tt1)
    log(f"trace: {len(calls)} engine calls in {tt1 - tt0:.3f} s; device "
        f"busy {summary.busy_s:.6f} s of {summary.window_s:.6f} s; kernel "
        f"seconds {summary.kernel_s}")
    return summary, calls, summary.breakdown()


def check_served(cell: Cell, seed: int, served: dict,
                 control: bool = False) -> dict:
    """The widest gap, over a sample of the served requests, between the
    reference's best logit and the logit of the token the system served."""
    chk = cell.config["check"]
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r][1]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([seed, 2])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :chk["sample"] - 1]]
    ref = importlib.import_module(
        f"bench.references.{cell.config['reference']}")
    t = time.perf_counter()
    out = ref.logit_gaps(cell.config, seed, [served[r][0] for r in pick],
                         [served[r][1] for r in pick], chk["pad_to"],
                         control=control)
    return {"value": out["gap"], "limit": chk["max_logit_gap"],
            "n_requests": len(pick), "n_tokens": out["n_tokens"],
            "control_gap": out.get("control_gap"),
            "seconds": time.perf_counter() - t}
