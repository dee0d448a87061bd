"""``kernels/pofx_matmul.py``: x (m, k) in bfloat16 @ decode(codes (k, n),
one byte each) * scale (n,) in float32 -> (m, n) in float32."""
from __future__ import annotations


def work(m: int, k: int, n: int) -> tuple:
    """(FLOPs, HBM bytes) of one call with ``m`` live rows."""
    flops = 2.0 * m * k * n
    nbytes = k * n + 2.0 * m * k + 4.0 * n + 4.0 * m * n
    return flops, nbytes


def decode_step_work(config: dict, live_rows: int) -> tuple:
    """(FLOPs, bytes) of the calls one decode step makes."""
    from bench.work.model import decode_matmuls
    f = b = 0.0
    for k, n in decode_matmuls(config):
        df, db = work(live_rows, k, n)
        f, b = f + df, b + db
    return f, b
