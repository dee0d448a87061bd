"""Scheduler: 90th percentile, over the requests submitted to the engine in
the window, of the seconds from ``submit`` to the start of the request's
first admission, both stamped by the engine (``ServeEngine.request(rid)``);
one not admitted by the window's end counts to the end. Unlike
``queue_wait_p90_s`` it leaves out how late the client submitted. None where
the engine has no per-request accessor."""
from bench.e2e import percentile


def read(r):
    request = getattr(r.client.engine, "request", None)
    if request is None:
        return None
    waits = []
    for rid in r.client.due:
        st = request(rid)
        if not r.t0 <= st.submitted_s <= r.t1:
            continue
        end = st.admitted_s if 0 <= st.admitted_s <= r.t1 else r.t1
        waits.append(end - st.submitted_s)
    return percentile(waits, 90)
