"""Prefill (``LM.prefill[_paged]`` on the device): host milliseconds the
engine waited for admissions' first tokens (its ``serve.admit.read`` span,
which ends when the prefill and the first-token sample are done) per
thousand tokens run through prefill (the ``prefill_tokens`` counter, shared
prefix excluded), over the window. None where the engine keeps no span
totals."""


def read(r):
    if "spans" not in r.stats1:
        return None
    tokens = r.stats1["prefill_tokens"] - r.stats0["prefill_tokens"]
    if not tokens:
        return None
    s0 = r.stats0["spans"].get("serve.admit.read", {"s": 0.0})["s"]
    s1 = r.stats1["spans"].get("serve.admit.read", {"s": 0.0})["s"]
    return (s1 - s0) * 1e3 / (tokens / 1e3)
