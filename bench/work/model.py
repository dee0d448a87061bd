"""The matmuls of one decode step of a dense GQA configuration, and the
model FLOPs of a decoded token."""
from __future__ import annotations

from typing import List, Tuple


def dims(config: dict) -> dict:
    return dict(d=config["hidden_size"], H=config["num_attention_heads"],
                G=config["num_key_value_heads"], Dh=config["head_dim"],
                ff=config["intermediate_size"], V=config["vocab_size"],
                L=config["num_hidden_layers"])


def decode_matmuls(config: dict) -> List[Tuple[int, int]]:
    """(k, n) of every weight matmul one decoded token passes through:
    q, k, v, o, gate, up and down of each layer, then the output head."""
    m = dims(config)
    d, H, G, Dh, ff = m["d"], m["H"], m["G"], m["Dh"], m["ff"]
    layer = [(d, H * Dh), (d, G * Dh), (d, G * Dh), (H * Dh, d),
             (d, ff), (d, ff), (ff, d)]
    return layer * m["L"] + [(d, m["V"])]


def token_flops(config: dict, context: int) -> float:
    """Model FLOPs of decoding one token that attends to ``context``
    positions: 2 per weight of every matmul, plus scores and weighted
    values (2 * 2 * heads * head_dim per position) in every layer."""
    m = dims(config)
    weights = sum(k * n for k, n in decode_matmuls(config))
    return 2.0 * weights + 4.0 * m["L"] * m["H"] * m["Dh"] * context
