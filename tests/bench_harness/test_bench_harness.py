"""CPU tests of the benchmark's yardstick: traffic, end-to-end arithmetic,
trace reduction, work counts, the chip check and the plain reference.

    PYTHONPATH=src python -m pytest -q tests/bench_harness
"""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import e2e
from bench.cell import load_benchmark
from bench.generator import (generate, load_mix, lognormal_quantiles,
                             n_requests, prompt_lengths)

ROOT = Path(__file__).resolve().parents[2]
BENCH = load_benchmark(ROOT)
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


# -- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("mix_name", MIXES)
def test_traffic_deterministic_per_seed(mix_name):
    mix = load_mix(mix_name)
    a = generate(mix, 2**31 + 99, 64000, 32)
    b = generate(mix, 2**31 + 99, 64000, 32)
    c = generate(mix, 5, 64000, 32)
    assert [(x.rid, x.due_s, x.max_new) for x in a] == \
        [(x.rid, x.due_s, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix_name", MIXES)
def test_traffic_lengths_and_gaps_in_range(mix_name):
    mix = load_mix(mix_name)
    lo, hi = prompt_lengths(mix)
    out = mix["output"]
    for seed in (1, 2, 3):
        reqs = generate(mix, seed, 64000, 32)
        assert len(reqs) == n_requests(mix, 32)
        for r in reqs:
            assert lo <= len(r.prompt) <= hi
            assert out["min"] <= r.max_new <= out["max"]
            assert r.prompt.min() >= 0 and r.prompt.max() < 64000
        due = np.array([r.due_s for r in reqs])
        assert np.all(np.diff(due) >= 0)
        arr = mix["arrivals"]
        if arr["process"] == "gamma":
            b0 = arr["initial_backlog"]
            assert np.all(due[:b0] == 0)
            gaps = (np.diff(due[b0 - 1:]) if b0
                    else np.diff(due, prepend=0.0))
            assert np.all(gaps >= 0)
            # the mean rate is exact per block, and the gaps' CV near the
            # mix's (2 is bursty, 1 Poisson; a block's quantiles leave out
            # the farthest tail)
            k = mix["block"]
            assert math.isclose(gaps[:k].mean(), 1 / arr["rate_per_s"],
                                rel_tol=1e-9)
            cv = gaps[:k].std() / gaps[:k].mean()
            assert 0.85 * arr["cv"] < cv <= arr["cv"]
        else:
            assert np.all(due == 0)


@pytest.mark.parametrize("mix_name", MIXES)
def test_traffic_same_work_every_seed(mix_name):
    """Each block of requests asks for the same lengths: the quantiles of
    the mix's distributions."""
    mix = load_mix(mix_name)
    k = mix["block"]
    base = mix["prompt"].get("shared", {}).get("tokens", 0)
    a = generate(mix, 11, 64000, 32)[:k]
    assert sorted(x.max_new for x in a) == \
        sorted(lognormal_quantiles(mix["output"], k))
    assert sorted(len(x.prompt) - base for x in a) == \
        sorted(lognormal_quantiles(mix["prompt"]["unique"], k))


def test_shared_prefixes_are_shared():
    mix = load_mix("chat_shared")
    reqs = generate(mix, 4, 102400, 32)
    t = mix["prompt"]["shared"]["tokens"]
    by_id = {}
    for r in reqs:
        by_id.setdefault(r.shared_id, set()).add(r.prompt[:t].tobytes())
    assert len(by_id) == mix["prompt"]["shared"]["count"]
    assert all(len(v) == 1 for v in by_id.values())


# -- end-to-end arithmetic ---------------------------------------------------

def _steady(n_req=4, dt=0.4, t_end=10.0, stall_at=None, stall=0.0):
    """Every ``dt`` each request gets one 8-token batch; a stall shifts
    every batch after ``stall_at`` by ``stall`` seconds."""
    batches = {}
    for rid in range(n_req):
        t, out = 0.1 * rid, []
        while t < t_end:
            tt = t + (stall if stall_at is not None and t >= stall_at else 0)
            out.append((tt, 8))
            t += dt
        batches[rid] = out
    return batches


def test_rate_is_over_the_whole_window():
    b = {0: [(1.0, 10), (2.0, 10)], 1: [(9.5, 30)]}
    assert e2e.output_tok_per_s(b, 0.0, 10.0) == pytest.approx(5.0)
    # tokens outside (t0, t1] do not count, the time still does
    assert e2e.output_tok_per_s(b, 1.0, 11.0) == pytest.approx(4.0)


def test_tpot_counts_tokens_after_the_first_batch():
    b = {0: [(1.0, 8), (2.0, 8), (3.0, 8)]}
    assert e2e.tpot_samples(b, 0.0, 10.0) == [pytest.approx(2.0 / 16)]
    assert e2e.tpot_samples({0: [(1.0, 8)]}, 0.0, 10.0) == []


def test_tail_is_over_all_requests():
    slow = {rid: [(0.0, 1), (1.0 if rid else 9.0, 1)] for rid in range(10)}
    samples = e2e.tpot_samples(slow, -1.0, 10.0)
    assert len(samples) == 10 and max(samples) == 9.0
    assert e2e.percentile(samples, 100) == 9.0


def test_a_stall_moves_tpot_and_ttft():
    calm = e2e.end_to_end(_steady(), {}, {}, 0.0, 10.0)
    stalled_b = _steady(stall_at=5.0, stall=5.0, t_end=10.0)
    stalled = e2e.end_to_end(stalled_b, {}, {}, 0.0, 15.0)
    assert stalled["tpot_p90_ms"] > calm["tpot_p90_ms"] * 1.5
    due = {rid: 4.9 + 0.01 * rid for rid in range(5)}
    first_calm = {rid: d + 0.3 for rid, d in due.items()}
    first_stalled = {rid: d + 5.3 for rid, d in due.items()}
    t_calm = e2e.ttft_samples(due, first_calm, 0.0, 15.0)
    t_stall = e2e.ttft_samples(due, first_stalled, 0.0, 15.0)
    assert e2e.percentile(t_stall, 90) > e2e.percentile(t_calm, 90) + 4.9


def test_unserved_request_counts_as_a_miss():
    due = {0: 1.0, 1: 2.0, 2: 30.0}
    first = {0: 1.5}
    got = e2e.ttft_samples(due, first, 0.0, 10.0)
    assert sorted(got) == [0.5, 8.0]            # rid 2 is not due in it
    out = e2e.end_to_end({}, due, first, 0.0, 10.0)
    assert out["n_ttft_missed"] == 1 and out["ttft_p90_s"] > 7.0


# -- work counts ---------------------------------------------------------------

def test_work_at_yi9b_shapes():
    from bench.work import kv_flash_decode, model, pofx_matmul
    cfg = json.loads((ROOT / "bench/configs/yi9b.json").read_text())
    mms = model.decode_matmuls(cfg)
    assert len(mms) == 48 * 7 + 1
    weights = sum(k * n for k, n in mms)
    # q 4096x4096, k and v 4096x512, o 4096x4096, gate/up/down 4096x11008
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert weights == 48 * per_layer + 4096 * 64000
    f, b = pofx_matmul.work(32, 11008, 4096)
    assert f == 2 * 32 * 11008 * 4096
    assert b == 11008 * 4096 + 2 * 32 * 11008 + 4 * 4096 + 4 * 32 * 4096
    f, b = kv_flash_decode.work(32, 4, 128, [100, 300])
    assert f == 4 * 32 * 128 * 400
    assert b == 2 * 4 * 128 * 400 + 2 * (2 * 4 * 128 * 4 + 2 * 32 * 128 * 4)
    assert model.token_flops(cfg, 10) == 2 * weights + 4 * 48 * 32 * 128 * 10


# -- the chip check -----------------------------------------------------------

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_refuses_cpu():
    from bench.device import NoChip, require_chips
    with pytest.raises(NoChip):
        require_chips(1, [_Dev("cpu", "cpu")])
    with pytest.raises(NoChip):
        require_chips(4, [_Dev("tpu", "TPU v5 lite")])


def test_refuses_unknown_device_kind():
    from bench.device import require_chips
    with pytest.raises(ValueError, match="no published peaks"):
        require_chips(1, [_Dev("tpu", "TPU v9 imaginary")])
    _, peaks = require_chips(1, [_Dev("tpu", "TPU v5 lite")])
    assert peaks.hbm_bytes_per_s == 819e9 and peaks.bf16_flops == 197e12


def test_run_exits_nonzero_without_a_chip(capsys):
    from bench import run
    rc = run.main(["--workload", BENCH["workloads"][0]["name"], "--seed",
                   "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("mix_name", MIXES)
def test_schedule_is_the_mixs_own(mix_name):
    """Every mix replays its own schedule whatever the run's seed: only the
    token ids follow the seed."""
    mix = load_mix(mix_name)
    a = generate(mix, 1, 102400, 32)
    b = generate(mix, 2, 102400, 32)
    key = lambda r: (r.due_s, r.max_new, len(r.prompt), r.shared_id)
    assert [key(x) for x in a] == [key(x) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


# -- trace reduction -----------------------------------------------------------

SAMPLE = ROOT / "bench/testdata/trace_sample.json"


@pytest.mark.skipif(not SAMPLE.exists(), reason="no recorded trace sample")
def test_trace_reduction_on_recorded_sample():
    """On a slice of a chip trace: the busy time is the union of the device
    ops, busy plus every idle gap is the window, each kernel is found, and
    each gap is labelled by the host span open during it."""
    from bench import trace as tr
    c = json.loads(SAMPLE.read_text())
    s = tr.summarize(c)
    (lo, dur), = [(h[1], h[2]) for h in c["host"] if h[0] == "bench.traced"]
    hi = lo + dur
    # busy by a plain sweep over the clipped ops that contain no other op
    ops = [(max(st, lo), min(st + d, hi)) for _, st, d, _ in c["device"]
           if st < hi and st + d > lo]
    leaves = [(a, b) for i, (a, b) in enumerate(ops)
              if not any(j != i and a <= x and y <= b and (x, y) != (a, b)
                         for j, (x, y) in enumerate(ops))]
    busy, end = 0.0, lo
    for a, b in sorted(leaves):
        if b > end:
            busy += b - max(a, end)
            end = b
    assert len(leaves) < len(ops)          # the sample holds a while loop
    assert s.window_s == pytest.approx(dur / 1e9)
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    gaps = tr.summarize(c, max_gaps=None).idle_gaps
    assert sum(g for _, g in gaps) + s.busy_s == pytest.approx(s.window_s)
    spans = {h[0] for h in c["host"]} | {"no span"}
    assert all(label in spans for label, _ in gaps)
    want = json.loads((ROOT / "bench/testdata/trace_sample.expect.json")
                      .read_text())
    assert sorted(s.kernel_s) == want["kernels"]
    for k, v in want["kernel_s"].items():
        assert s.kernel_s[k] == pytest.approx(v, rel=1e-9)
    assert len(s.top_ops) <= 10 and len(s.idle_gaps) <= 10
