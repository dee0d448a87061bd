"""Cache (``launch/paging.py``): share of the prompt tokens admitted in the
window that were served from shared pages (the engine's
``prefix_hit_tokens`` counter, read around each admission)."""


def read(r):
    prompt = sum(c.prompt_tokens for c in r.calls if c.kind == "admit")
    if not prompt:
        return None
    hit = sum(c.hit_tokens for c in r.calls if c.kind == "admit")
    return 100.0 * hit / prompt
