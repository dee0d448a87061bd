"""Decode step: the model FLOPs of the tokens decoded in the window (every
weight of every matmul, output head included, and attention over each
token's live context) over the engine's decode time and the chip's bf16
peak."""
from bench.work.model import token_flops


def read(r):
    if not r.decode_time:
        return None
    flops = sum(token_flops(r.config, n) for c in r.calls if c.kind == "step"
                for ls in c.lengths for n in ls)
    return 100.0 * flops / r.decode_time / r.peaks.bf16_flops
