"""Scheduler: 90th percentile, over the requests due in the window, of the
seconds from due to the start of the ``admit_ready`` call that admitted the
request; one not admitted by the window's end counts to the end."""
from bench.e2e import percentile


def read(r):
    c = r.client
    waits = [min(c.admit_started.get(rid, r.t1), r.t1) - due
             for rid, due in c.due.items() if r.t0 <= due <= r.t1]
    return percentile(waits, 90)
