"""The serving engine's spans and counters (``launch/tracing.py``).

Spans land in the profiler's trace, nested as the engine nests them, with
request ids on admission spans; their running totals, the time properties
built on them and the engine's counters are checked against the trace, a
fake clock and an independent recount. ``stats()`` must stay O(1) in the
requests served and the prefix index's size.
"""
import glob

import jax
import numpy as np
import pytest

from differential import make_engine, make_prompt
from repro.launch import paging, tracing
from repro.launch.engine import Request
from repro.launch.paging import PagedKVManager

SPANS = {"serve.run", "serve.admit", "serve.admit.plan", "serve.admit.prefill",
         "serve.admit.read", "serve.step", "serve.step.inputs",
         "serve.step.launch", "serve.step.read", "serve.step.emit"}
PARENT = {"serve.admit.plan": "serve.admit",
          "serve.admit.prefill": "serve.admit",
          "serve.admit.scatter": "serve.admit",
          "serve.admit.read": "serve.admit",
          "serve.step.inputs": "serve.step",
          "serve.step.launch": "serve.step",
          "serve.step.read": "serve.step",
          "serve.step.emit": "serve.step",
          "serve.admit": "serve.run", "serve.step": "serve.run"}
# the benchmark's trace reduction matches these in host spans and op names
RESERVED = ("pofx_matmul", "kv_flash_decode", "kv_flash_paged_decode")


@pytest.fixture(scope="module")
def dense(tiny):
    return tiny("yi-9b")


def _workload(vocab, n=5, shared=0, max_new=(1, 4, 6, 3, 5)):
    """``n`` requests; the first ``shared`` prompt tokens are common."""
    rng = np.random.default_rng(0)
    system = rng.integers(0, vocab, shared)
    return [Request(rid=i, prompt=np.concatenate(
        [system, make_prompt(i, 5 + 2 * i, vocab)]),
        max_new=max_new[i % len(max_new)]) for i in range(n)]


def _engine(dense, paged):
    cfg, model, params = dense
    kw = dict(paged=True, page_size=4) if paged else {}
    return make_engine(model, params, n_slots=2, chunk=3, **kw)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), line.name))
    return out


def _inside(child, parent):
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


@pytest.mark.parametrize("paged", [False, True])
def test_spans_in_profiler_trace_nest_and_match_totals(dense, paged,
                                                       tmp_path):
    cfg = dense[0]
    eng = _engine(dense, paged)
    reqs = _workload(cfg.vocab_size, shared=9 if paged else 0)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    want = SPANS | ({"serve.admit.scatter"} if not paged else set())
    assert names == want
    for name in names:
        assert name.startswith("serve.")
        assert not any(k in name for k in RESERVED)
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    # every child lies inside one span of its parent's name, on its thread
    for s in spans:
        if s[0] in PARENT:
            assert sum(_inside(s, p) for p in by_name[PARENT[s[0]]]) == 1, s
    # admission spans carry the request's id and its token counts
    admits = by_name["serve.admit"]
    assert sorted(a[3]["rid"] for a in admits) == [r.rid for r in reqs]
    for a in admits:
        req = reqs[a[3]["rid"]]
        assert a[3]["prompt_tokens"] == len(req.prompt)
        assert 0 <= a[3]["prefix_tokens"] < len(req.prompt)
    if paged:   # later requests share the first one's system prompt
        assert sum(a[3]["prefix_tokens"] > 0 for a in admits) >= 3
    for i, s in enumerate(sorted(by_name["serve.step"], key=lambda s: s[1])):
        assert s[3]["step_num"] == i
        assert 1 <= s[3]["live"] <= eng.n_slots and s[3]["steps"] >= 1
    # the totals stats() reports count exactly the spans the trace holds
    st = eng.stats()
    assert {k: v["n"] for k, v in st["spans"].items()} == \
        {k: len(v) for k, v in by_name.items()}
    for name, v in st["spans"].items():
        traced = sum(s[2] - s[1] for s in by_name[name]) / 1e9
        assert v["s"] <= traced + 1e-3 * v["n"]
    assert st["spans"]["serve.admit"]["n"] == st["admissions"] == len(reqs)
    assert st["spans"]["serve.step"]["n"] == eng.n_chunks
    assert eng.decode_time == pytest.approx(
        st["spans"]["serve.step.launch"]["s"]
        + st["spans"]["serve.step.read"]["s"])
    assert eng.prefill_time == st["spans"]["serve.admit"]["s"]


class _Clock:
    """A clock for the spans that moves only when told to."""

    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.ns

    def wrap(self, fn, ns):
        def timed(*a, **k):
            self.ns += ns
            return fn(*a, **k)
        return timed


def test_decode_and_prefill_time_are_the_old_intervals(dense, monkeypatch):
    """``decode_time`` runs from the chunk's launch to its tokens on the
    host; ``prefill_time`` from the admission's plan (paged) to its first
    token. Host work before a launch (page allocation) and after an
    admission's first token (retiring a request) counts in neither."""
    cfg = dense[0]
    eng = _engine(dense, paged=True)
    eng.run(_workload(cfg.vocab_size, shared=9))        # compile first
    clock = _Clock()
    monkeypatch.setattr(tracing, "perf_counter_ns", clock)
    pager = eng._pager
    LAUNCH, PLAN, PREFILL, SAMPLE, ENSURE, RETIRE = 1, 10, 100, 1000, 1e4, 1e5
    monkeypatch.setattr(eng, "_chunk_fn", clock.wrap(eng._chunk_fn, LAUNCH))
    monkeypatch.setattr(pager, "admit", clock.wrap(pager.admit, PLAN))
    monkeypatch.setattr(eng, "_prefill_paged_fn",
                        clock.wrap(eng._prefill_paged_fn, PREFILL))
    monkeypatch.setattr(eng, "_sample_fn", clock.wrap(eng._sample_fn, SAMPLE))
    monkeypatch.setattr(pager, "ensure", clock.wrap(pager.ensure, ENSURE))
    monkeypatch.setattr(pager, "suspend", clock.wrap(pager.suspend, RETIRE))
    before = eng.stats()
    eng.run([Request(rid=100 + r.rid, prompt=r.prompt, max_new=r.max_new)
             for r in _workload(cfg.vocab_size, shared=9)])
    st = eng.stats()
    chunks = (st["spans"]["serve.step"]["n"]
              - before["spans"]["serve.step"]["n"])
    admits = st["admissions"] - before["admissions"]
    assert chunks > 0 and admits == 5
    assert st["decode_time_s"] - before["decode_time_s"] == \
        pytest.approx(chunks * LAUNCH / 1e9)
    assert st["prefill_time_s"] - before["prefill_time_s"] == \
        pytest.approx(admits * (PLAN + PREFILL + SAMPLE) / 1e9)


def test_dense_prefill_time_holds_the_cache_init(dense, monkeypatch):
    cfg, model, _ = dense
    eng = _engine(dense, paged=False)
    eng.run(_workload(cfg.vocab_size))                   # compile first
    clock = _Clock()
    monkeypatch.setattr(tracing, "perf_counter_ns", clock)
    monkeypatch.setattr(model, "init_cache", clock.wrap(model.init_cache, 7))
    monkeypatch.setattr(eng, "_scatter_fn", clock.wrap(eng._scatter_fn, 70))
    before = eng.stats()
    eng.run([Request(rid=10 + r.rid, prompt=r.prompt, max_new=r.max_new)
             for r in _workload(cfg.vocab_size)])
    st = eng.stats()
    assert st["prefill_time_s"] - before["prefill_time_s"] == \
        pytest.approx(5 * 77 / 1e9)
    assert st["decode_time_s"] == before["decode_time_s"]
    plan = st["spans"]["serve.admit.plan"]["s"]
    assert plan - before["spans"]["serve.admit.plan"]["s"] == \
        pytest.approx(5 * 7 / 1e9)


@pytest.mark.parametrize("paged", [False, True])
def test_counters_are_running_totals(dense, paged):
    cfg = dense[0]
    eng = _engine(dense, paged)
    reqs = _workload(cfg.vocab_size, shared=9 if paged else 0)
    done = eng.run(reqs)
    # evict and resume one request: its resumed context is prefilled again
    eng.submit(Request(rid=50, prompt=make_prompt(9, 7, cfg.vocab_size),
                       max_new=9))
    eng.admit_ready()
    eng.step()
    n_evicted = len(eng.request(50).out)
    eng.evict(50)
    eng.admit_ready()
    while eng.active_rids:
        eng.step()
    states = [eng.request(r.rid) for r in reqs] + [eng.request(50)]
    st = eng.stats()
    assert st["generated_tokens"] == sum(len(s.out) for s in states)
    assert st["decode_tokens"] == st["generated_tokens"] - len(states) - 1
    assert st["admissions"] == len(states) + 1
    hit = st.get("prefix_hit_tokens", 0)
    ctx = sum(len(r.prompt) for r in reqs) + 7 + len(states[-1].context)
    assert st["admitted_prompt_tokens"] == ctx
    assert st["prefill_tokens"] == ctx - hit
    # every decoded token is one live slot-step
    assert st["live_slot_steps"] == st["decode_tokens"]
    # each decoded token attended its prompt and every earlier token; the
    # token sampled at the resume came from prefill
    want = sum(len(s.req.prompt) + k for s in states
               for k in range(1, len(s.out))) - (7 + n_evicted)
    assert st["live_context_sum"] == want
    progs = st["programs"]
    assert progs["decode_chunk"] >= 1 and progs["sample"] >= 1
    if paged:
        # each distinct shared-prefix length is a prefill program
        assert progs["prefill_paged"] >= 2
        assert {"copy_page"} <= set(progs) and "prefill" not in progs
    else:
        assert progs["prefill"] >= 1 and progs["scatter"] == 1
    assert all(s.done for s in done)


def test_request_accessor_stamps_submit_and_first_admission(dense):
    cfg = dense[0]
    eng = _engine(dense, paged=False)
    req = Request(rid=3, prompt=make_prompt(3, 6, cfg.vocab_size), max_new=8)
    eng.submit(req)
    st = eng.request(3)
    assert st.req is req and st.submitted_s > 0 and st.admitted_s < 0
    eng.admit_ready()
    first = st.admitted_s
    assert first >= st.submitted_s
    eng.step()
    eng.evict(3)
    eng.admit_ready()
    assert st.admitted_s == first and st.n_evictions == 1
    with pytest.raises(KeyError):
        eng.request(4)


class _NoWalk(dict):
    def _refuse(self, *a):
        raise AssertionError("stats() walked the requests")
    __iter__ = values = items = keys = _refuse


def test_stats_does_not_walk_requests_or_the_index(dense, monkeypatch):
    cfg = dense[0]
    eng = _engine(dense, paged=True)
    eng.run(_workload(cfg.vocab_size, n=8, shared=9))
    want = eng._pager.index.resident_tokens()
    monkeypatch.setattr(eng, "_states", _NoWalk(eng._states))

    def refuse(self, *a):
        raise AssertionError("stats() walked the prefix index")
    monkeypatch.setattr(paging.RadixPrefixIndex, "resident_tokens", refuse)
    monkeypatch.setattr(paging.RadixPrefixIndex, "_droppable", refuse)
    st = eng.stats()
    assert st["index_resident_tokens"] == want > 0
    assert st["requests"] == 8


@pytest.mark.parametrize("seed", range(4))
def test_index_resident_tokens_kept_equals_walk(seed):
    """Random admit / grow / register / evict / release under a small pool,
    so the index reclaims holdings; ``check()`` compares the kept count with
    the walk after every operation."""
    rng = np.random.default_rng(seed)
    ps, max_pages = 4, 8
    mgr = PagedKVManager(n_pages=20, page_size=ps, max_pages=max_pages,
                         spec_key="fxp8")
    systems = [rng.integers(0, 5, 9).tolist() for _ in range(3)]
    live, toks, rid = {}, {}, 0
    for _ in range(200):
        op = rng.integers(4)
        if op == 0 or not live:
            t = systems[rng.integers(3)] + rng.integers(
                0, 5, rng.integers(1, 12)).tolist()
            try:
                mgr.admit(rid, t, len(t))
            except RuntimeError:
                continue              # pool exhausted: nothing admitted
            mgr.register(rid, t, len(t))
            live[rid], toks[rid] = len(t), t
            rid += 1
        else:
            r = list(live)[rng.integers(len(live))]
            if op == 1 and live[r] + 3 <= max_pages * ps:
                try:
                    mgr.ensure(r, live[r] + 3)
                except RuntimeError:
                    continue
                toks[r] = toks[r] + rng.integers(0, 5, 3).tolist()
                live[r] += 3
            elif op == 2:
                mgr.suspend(r, toks[r], live.pop(r))
            else:
                mgr.release(r)
                live.pop(r)
        mgr.check()
        assert mgr.stats()["index_resident_tokens"] == \
            mgr.index.resident_tokens()
    assert mgr.pages_reclaimed > 0
