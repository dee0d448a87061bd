"""Scheduler (``ServeEngine.step``): host milliseconds per decode chunk
during which the device has nothing queued: the engine's ``serve.step``
span less its ``serve.step.read`` (the blocking read of the chunk's tokens),
per chunk, over the window. None where the engine keeps no span totals."""


def _span(r, name):
    """(count, seconds) of span ``name`` over the window, or None."""
    if "spans" not in r.stats1:
        return None
    s0 = r.stats0["spans"].get(name, {"n": 0, "s": 0.0})
    s1 = r.stats1["spans"].get(name, {"n": 0, "s": 0.0})
    return s1["n"] - s0["n"], s1["s"] - s0["s"]


def read(r):
    step, wait = _span(r, "serve.step"), _span(r, "serve.step.read")
    if step is None or not step[0]:
        return None
    return (step[1] - wait[1]) / step[0] * 1e3
