"""Host spans of the serving engine, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
running it lands in the trace beside the device planes, on their clock, with
its keyword ids (``rid=...``) as metadata. Whether or not a trace runs, the
span also adds its duration and a count to running totals, which the engine
reports from ``stats()``. With the profiler off a span costs one
TraceAnnotation and two clock reads.

Span names start with ``serve.``. The benchmark's trace reduction matches
host spans that start with ``bench.`` and device ops whose names contain a
kernel's name, so no span may do either.
"""
from __future__ import annotations

from time import perf_counter_ns
from typing import Dict, List

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["SpanTotals"]


class _Span:
    __slots__ = ("_totals", "_name", "annotation", "_t0")

    def __init__(self, totals: Dict[str, List[int]], name: str, annotation):
        self._totals, self._name, self.annotation = totals, name, annotation

    def __enter__(self) -> "_Span":
        self.annotation.__enter__()
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dt = perf_counter_ns() - self._t0
        self.annotation.__exit__(*exc)
        tot = self._totals.setdefault(self._name, [0, 0])
        tot[0] += 1
        tot[1] += dt


class SpanTotals:
    """Running ``[count, nanoseconds]`` of every span, by name."""

    def __init__(self):
        self.totals: Dict[str, List[int]] = {}

    def span(self, name: str, **ids) -> _Span:
        return _Span(self.totals, name, TraceAnnotation(name, **ids))

    def step(self, name: str, step_num: int, **ids) -> _Span:
        """A span the profiler marks as a step (``StepTraceAnnotation``)."""
        return _Span(self.totals, name,
                     StepTraceAnnotation(name, step_num=step_num, **ids))

    def seconds(self, name: str) -> float:
        return self.totals.get(name, (0, 0))[1] / 1e9

    def report(self) -> Dict[str, Dict[str, float]]:
        return {name: {"n": n, "s": ns / 1e9}
                for name, (n, ns) in self.totals.items()}
