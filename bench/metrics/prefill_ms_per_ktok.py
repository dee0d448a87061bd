"""Prefill (``ServeEngine._admit`` -> ``LM.prefill[_paged]``): host
milliseconds of the window's ``admit_ready`` calls per thousand prompt
tokens they actually prefilled (shared-prefix tokens excluded)."""


def read(r):
    admits = [c for c in r.calls if c.kind == "admit"]
    tokens = sum(c.prompt_tokens - c.hit_tokens for c in admits)
    if not tokens:
        return None
    return sum(c.t1 - c.t0 for c in admits) * 1e3 / (tokens / 1e3)
