"""``kernels/kv_flash_paged_decode.py``: the decode of ``kv_flash_decode``
read through a block table over a shared page pool; scales are global per
layer. Only each slot's live context counts."""
from __future__ import annotations

from typing import Sequence


def work(heads: int, kv_heads: int, head_dim: int,
         lengths: Sequence[int]) -> tuple:
    ctx = float(sum(lengths))
    n = len(lengths)
    flops = 4.0 * heads * head_dim * ctx
    nbytes = (2.0 * kv_heads * head_dim * ctx
              + 2 * kv_heads * head_dim * 4               # global scales
              + n * 2 * heads * head_dim * 4)             # q in, out
    return flops, nbytes


def decode_step_work(config: dict, lengths: Sequence[int]) -> tuple:
    """Every attention layer's call; a windowed layer's slots attend to
    min(length, window) positions."""
    from bench.work.model import attended, attention_layers, dims
    m = dims(config)
    f = b = 0.0
    for layers, window in attention_layers(config):
        lf, lb = work(m["H"], m["G"], m["Dh"],
                      [attended(n, window) for n in lengths])
        f, b = f + layers * lf, b + layers * lb
    return f, b
