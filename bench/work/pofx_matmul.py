"""``kernels/pofx_matmul.py``: x (m, k) in bfloat16 @ decode(codes (k, n),
one byte each) * scale (n,) in float32 -> (m, n) in float32."""
from __future__ import annotations


def work(m: int, k: int, n: int) -> tuple:
    """(FLOPs, HBM bytes) of one call with ``m`` live rows."""
    flops = 2.0 * m * k * n
    nbytes = k * n + 2.0 * m * k + 4.0 * n + 4.0 * m * n
    return flops, nbytes


def decode_step_work(config: dict, live_rows: int) -> tuple:
    """(FLOPs, bytes) of the weight matmuls one decode step makes in this
    kernel over ``live_rows`` rows: those of
    ``bench/work/model.py::step_matmuls`` the system runs through it
    (``Matmul.kernel``), each once; the router, the routed experts and
    mamba1's x and dt projections run elsewhere."""
    from bench.work.model import step_matmuls
    f = b = 0.0
    for mm in step_matmuls(config):
        if mm.kernel:
            df, db = work(live_rows, mm.k, mm.n)
            f, b = f + df, b + db
    return f, b
