"""CPU tests of the per-layer metrics that read the engine's own spans and
counters (``bench/metrics/``): each reader on a hand-built ``Record``, on
the record of an engine that keeps no spans (it reads nothing), and the
engine's live-slot counters against what the client derives from its calls.

    PYTHONPATH=src python -m pytest -q tests/bench_harness
"""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench.cell import metric_reader
from bench.harness import Record

NEW = ("chunk_host_ms", "admit_host_ms", "prefill_wait_ms_per_ktok",
       "pending_wait_p90_s")


def _stats(spans, prefill_tokens=0):
    return {"prefill_tokens": prefill_tokens,
            "spans": {k: {"n": n, "s": s} for k, (n, s) in spans.items()}}


def _record(stats0, stats1, engine=None, due=None, t0=100.0, t1=150.0):
    client = SimpleNamespace(engine=engine, due=due or {})
    return Record(config={}, n_slots=4, peaks=None, t0=t0, t1=t1,
                  client=client, calls=[], stats0=stats0, stats1=stats1,
                  decode_time=0.0, decode_steps=0)


def test_chunk_host_ms_is_host_time_per_chunk_outside_the_read():
    s0 = _stats({"serve.step": (10, 5.0), "serve.step.read": (10, 4.0)})
    s1 = _stats({"serve.step": (30, 15.0), "serve.step.read": (30, 13.6)})
    got = metric_reader("chunk_host_ms")(_record(s0, s1))
    assert got == pytest.approx((10.0 - 9.6) / 20 * 1e3)
    assert metric_reader("chunk_host_ms")(_record(s1, s1)) is None


def test_admit_host_ms_is_host_time_per_admission_outside_the_read():
    # spans that first appear inside the window count from zero
    s0 = _stats({})
    s1 = _stats({"serve.admit": (4, 2.0), "serve.admit.read": (4, 1.5)})
    got = metric_reader("admit_host_ms")(_record(s0, s1))
    assert got == pytest.approx(0.5 / 4 * 1e3)
    assert metric_reader("admit_host_ms")(_record(s1, s1)) is None


def test_prefill_wait_is_per_thousand_prefilled_tokens():
    s0 = _stats({"serve.admit.read": (2, 1.0)}, prefill_tokens=1000)
    s1 = _stats({"serve.admit.read": (5, 3.5)}, prefill_tokens=3000)
    got = metric_reader("prefill_wait_ms_per_ktok")(_record(s0, s1))
    assert got == pytest.approx(2.5 * 1e3 / 2.0)
    assert metric_reader("prefill_wait_ms_per_ktok")(_record(s1, s1)) is None


def test_pending_wait_counts_requests_submitted_in_the_window():
    st = {0: (90.0, 95.0),      # submitted before the window: left out
          1: (101.0, 103.0),    # 2 s
          2: (110.0, -1.0),     # never admitted: counts to t1, 40 s
          3: (120.0, 160.0),    # admitted after the window: 30 s
          4: (149.0, 149.5)}    # 0.5 s
    engine = SimpleNamespace(request=lambda rid: SimpleNamespace(
        submitted_s=st[rid][0], admitted_s=st[rid][1]))
    r = _record(_stats({}), _stats({}), engine=engine,
                due={rid: s for rid, (s, _) in st.items()})
    assert metric_reader("pending_wait_p90_s")(r) == \
        pytest.approx(np.percentile([2.0, 40.0, 30.0, 0.5], 90))


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_an_engine_without_spans(name):
    """An engine that reports no span totals and has no per-request
    accessor gives no reading, and no error."""
    old = {"prefill_tokens_absent": 0, "decode_steps": 5}
    r = _record(old, old, engine=SimpleNamespace(), due={1: 120.0})
    assert metric_reader(name)(r) is None


def _served(paged: bool, chunks: int):
    """A small engine driven by the benchmark's client for ``chunks``
    decode chunks, fewer than its requests need (an idle client would sleep
    to the deadline); returns (engine, client, stats before, stats
    after)."""
    from bench.generator import Arrival
    from bench.serve_loop import Client
    from repro.configs import ARCHS, RunConfig, smoke
    from repro.launch.engine import ServeEngine
    from repro.nn.models import build_model
    import jax
    cfg = smoke(ARCHS["yi-9b"])
    model = build_model(cfg, RunConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=3, max_len=64, chunk=4,
                         paged=paged, page_size=8)
    rng = np.random.default_rng(5)
    system = rng.integers(0, cfg.vocab_size, 12)
    arrivals = [
        Arrival(rid=i, due_s=0.0, max_new=int(m), prompt=np.concatenate(
            [system, rng.integers(0, cfg.vocab_size, 3 + 2 * i)]
        ).astype(np.int32))
        for i, m in enumerate([3, 9, 6, 11, 5, 7, 4])]
    client = Client(engine, arrivals, time.perf_counter())
    s0 = engine.stats()
    client.run(time.perf_counter() + 300.0, max_steps=chunks)
    return engine, client, s0, engine.stats()


@pytest.mark.parametrize("paged", [False, True])
def test_live_counters_match_the_clients_count(paged):
    """``live_slot_steps`` and ``live_context_sum`` count what the client's
    step calls record (``Call.lengths``), and the engine's admission spans
    lie inside the client's ``admit_ready`` calls."""
    engine, client, s0, s1 = _served(paged, chunks=3)
    steps = [c for c in client.calls if c.kind == "step"]
    assert len(steps) == 3 and engine.pending_rids
    lengths = [n for c in steps for ls in c.lengths for n in ls]
    assert s1["live_slot_steps"] - s0["live_slot_steps"] == len(lengths)
    assert s1["live_context_sum"] - s0["live_context_sum"] == sum(lengths)
    admits = [c for c in client.calls if c.kind == "admit"]
    assert s1["admissions"] == sum(len(c.rids) for c in admits)
    assert s1["prefill_tokens"] == sum(c.prompt_tokens - c.hit_tokens
                                       for c in admits)
    admit_s = s1["spans"]["serve.admit"]["s"]
    assert 0 < admit_s <= sum(c.t1 - c.t0 for c in admits)
    for rid in client.due:
        st = engine.request(rid)
        assert st.submitted_s >= client.submitted_at[rid]
        if st.admitted_s >= 0:
            assert st.admitted_s >= st.submitted_s
            assert st.admitted_s >= client.admit_started[rid]
