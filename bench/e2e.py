"""End-to-end metrics from what the client saw: when each request was due
and when each batch of its tokens came back. Pure arithmetic, no JAX.

A request's tokens come back in batches: the first with its admission, the
rest one decode chunk at a time. ``batches[rid]`` lists ``(t, n)``: ``n``
tokens received at host time ``t``. The window is ``(t0, t1]``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Batches = Dict[int, List[Tuple[float, int]]]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100), linear between order statistics;
    None for no values."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(batches: Batches, t0: float, t1: float) -> Batches:
    return {rid: [(t, n) for t, n in bs if t0 < t <= t1]
            for rid, bs in batches.items()}


def output_tok_per_s(batches: Batches, t0: float, t1: float) -> float:
    """Every token received in the window over the whole window."""
    n = sum(n for bs in in_window(batches, t0, t1).values() for _, n in bs)
    return n / (t1 - t0)


def tpot_samples(batches: Batches, t0: float, t1: float) -> List[float]:
    """Seconds per output token, one sample per request that received
    tokens in two or more batches in the window: the time from its first
    batch in the window to its last, over the tokens that came after the
    first batch. (Tokens of one batch arrive together, so counting the
    first batch's own tokens would divide by tokens that took no time.)"""
    out = []
    for bs in in_window(batches, t0, t1).values():
        if len(bs) < 2:
            continue
        later = sum(n for _, n in bs[1:])
        out.append((bs[-1][0] - bs[0][0]) / later)
    return out


def ttft_samples(due: Dict[int, float], first: Dict[int, float],
                 t0: float, t1: float) -> List[float]:
    """Seconds from due to first token, for every request due in the
    window. One with no first token by the window's end counts as
    ``t1 - due``: a miss, at least that late."""
    out = []
    for rid, d in due.items():
        if not (t0 <= d <= t1):
            continue
        f = first.get(rid)
        out.append((f if f is not None and f <= t1 else t1) - d)
    return out


def end_to_end(batches: Batches, due: Dict[int, float],
               first: Dict[int, float], t0: float, t1: float) -> dict:
    """All end-to-end numbers of one window, with their sample counts."""
    tpot = tpot_samples(batches, t0, t1)
    ttft = ttft_samples(due, first, t0, t1)
    return {
        "output_tok_per_s": output_tok_per_s(batches, t0, t1),
        "tpot_p90_ms": None if not tpot else percentile(tpot, 90) * 1e3,
        "ttft_p90_s": percentile(ttft, 90),
        "n_tpot": len(tpot),
        "n_ttft": len(ttft),
        "n_ttft_missed": sum(1 for rid, d in due.items()
                             if t0 <= d <= t1
                             and not (first.get(rid) is not None
                                      and first[rid] <= t1)),
    }
