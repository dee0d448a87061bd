"""Decode step layer (``ServeEngine._chunk_fn`` -> ``LM.decode_step``):
milliseconds per decode step over the window, from the engine's own
``decode_time`` / ``decode_steps`` counters (host clock around each chunk,
which ends in the blocking read of its tokens)."""


def read(r):
    if not r.decode_steps:
        return None
    return r.decode_time / r.decode_steps * 1e3
