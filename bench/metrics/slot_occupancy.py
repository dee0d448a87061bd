"""Scheduler (``launch/engine.py``): mean share of the engine's slots that
decode a token for a request, over the decode steps of the window."""


def read(r):
    steps = sum(c.steps for c in r.calls if c.kind == "step")
    if not steps:
        return None
    live = sum(len(ls) for c in r.calls if c.kind == "step"
               for ls in c.lengths)
    return 100.0 * live / (steps * r.n_slots)
