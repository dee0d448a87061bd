"""The client: submits each request when it falls due on the host clock and
drives the engine's public calls (``submit``, ``admit_ready``, ``step``),
recording when every token came back. Each call into the engine is a host
span (``jax.profiler.TraceAnnotation``) that a traced run finds again.

The engine keeps a request's tokens in ``ServeEngine._states[rid].out``;
that dict is the one internal attribute read here (the engine has no public
per-step token hook)."""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from bench.generator import Arrival


@dataclasses.dataclass
class Call:
    kind: str                    # "admit" or "step"
    t0: float
    t1: float
    rids: List[int] = dataclasses.field(default_factory=list)
    prompt_tokens: int = 0       # admit: prompt tokens admitted
    hit_tokens: int = 0          # admit: of those, served from shared pages
    steps: int = 0               # step: decode steps the chunk ran
    live: int = 0                # step: slots holding a request at its start
    lengths: List[List[int]] = dataclasses.field(default_factory=list)
    #   step: per decode step, the attended length of each slot that still
    #   decodes a token for its request in that step
    tokens: int = 0              # tokens the client received from the call


class Client:
    def __init__(self, engine, arrivals: Sequence[Arrival], t_base: float):
        self.engine = engine
        self.arrivals = sorted(arrivals, key=lambda a: (a.due_s, a.rid))
        self.t_base = t_base
        self._next = 0
        self.due: Dict[int, float] = {}
        self.submitted_at: Dict[int, float] = {}
        self.first: Dict[int, float] = {}
        self.batches: Dict[int, list] = defaultdict(list)
        self.admit_started: Dict[int, float] = {}
        self.calls: List[Call] = []
        self._seen: Dict[int, int] = {}

    # -- bookkeeping --------------------------------------------------------

    def _states(self):
        return self.engine._states

    def _collect(self, t: float) -> int:
        n_new = 0
        for rid in list(self.due):
            st = self._states().get(rid)
            if st is None:
                continue
            n = len(st.out)
            seen = self._seen.get(rid, 0)
            if n > seen:
                self.batches[rid].append((t, n - seen))
                self.first.setdefault(rid, t)
                self._seen[rid] = n
                n_new += n - seen
        return n_new

    def _submit_due(self, now: float) -> None:
        from repro.launch.engine import Request
        while (self._next < len(self.arrivals)
               and self.t_base + self.arrivals[self._next].due_s <= now):
            a = self.arrivals[self._next]
            rid = a.rid
            self.engine.submit(Request(rid=rid, prompt=a.prompt,
                                       max_new=a.max_new))
            self.due[rid] = self.t_base + a.due_s
            self.submitted_at[rid] = now
            self._next += 1

    def _live_lengths(self) -> tuple:
        """(live slots, remaining budget and attended length of each)."""
        eng = self.engine
        out = []
        for rid in eng.active_rids:
            st = self._states()[rid]
            prompt = len(st.req.prompt)
            limit = min(st.req.max_new, eng.max_len - prompt)
            pos = len(st.context) + len(st.out) - 1
            out.append((limit - len(st.out), pos))
        return out

    # -- the loop -----------------------------------------------------------

    def run(self, deadline: float, max_steps: Optional[int] = None) -> None:
        """Serve until the host clock passes ``deadline`` (checked before
        each call), or until ``max_steps`` decode chunks have run."""
        eng = self.engine
        n_steps = 0
        while True:
            now = time.perf_counter()
            if now >= deadline or (max_steps is not None
                                   and n_steps >= max_steps):
                return
            self._submit_due(now)
            if eng.pending_rids and eng.free_slots:
                before = self._hit_tokens()
                pending = set(eng.pending_rids)
                with TraceAnnotation("bench.admit"):
                    t0 = time.perf_counter()
                    eng.admit_ready()
                    t1 = time.perf_counter()
                rids = sorted(pending - set(eng.pending_rids))
                for rid in rids:
                    self.admit_started[rid] = t0
                call = Call("admit", t0, t1, rids=rids,
                            prompt_tokens=sum(len(self._states()[r].context)
                                              for r in rids),
                            hit_tokens=self._hit_tokens() - before)
                call.tokens = self._collect(t1)
                self.calls.append(call)
                continue
            if eng.active_rids:
                live = self._live_lengths()
                steps0 = eng.decode_steps
                with TraceAnnotation("bench.step"):
                    t0 = time.perf_counter()
                    eng.step()
                    t1 = time.perf_counter()
                steps = eng.decode_steps - steps0
                call = Call("step", t0, t1, steps=steps, live=len(live),
                            lengths=[[pos + j + 1 for rem, pos in live
                                      if j < rem] for j in range(steps)])
                call.tokens = self._collect(t1)
                self.calls.append(call)
                n_steps += 1
                continue
            nxt = (self.t_base + self.arrivals[self._next].due_s
                   if self._next < len(self.arrivals) else deadline)
            with TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt, deadline) - now))

    def _hit_tokens(self) -> int:
        if not self.engine.paged:
            return 0
        return int(self.engine.stats()["prefix_hit_tokens"])

    def calls_in(self, t0: float, t1: float) -> List[Call]:
        return [c for c in self.calls if c.t0 >= t0 and c.t1 <= t1]
