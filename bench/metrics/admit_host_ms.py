"""Scheduler (``ServeEngine._admit*``): host milliseconds per admission
that are not the wait for the device: the engine's ``serve.admit`` span
(plan, copy-on-write, block table, prefill dispatch, first-token sample)
less its ``serve.admit.read`` (the blocking read of the first token), per
admission, over the window. None where the engine keeps no span totals."""


def _span(r, name):
    """(count, seconds) of span ``name`` over the window, or None."""
    if "spans" not in r.stats1:
        return None
    s0 = r.stats0["spans"].get(name, {"n": 0, "s": 0.0})
    s1 = r.stats1["spans"].get(name, {"n": 0, "s": 0.0})
    return s1["n"] - s0["n"], s1["s"] - s0["s"]


def read(r):
    admit, wait = _span(r, "serve.admit"), _span(r, "serve.admit.read")
    if admit is None or not admit[0]:
        return None
    return (admit[1] - wait[1]) / admit[0] * 1e3
