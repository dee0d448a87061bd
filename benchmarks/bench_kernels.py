"""Pallas kernels vs pure-jnp oracles: allclose sweep + throughput.

Kernels run in interpret mode on this CPU container (the TPU lowering is
exercised by BlockSpec construction either way); correctness is the
contract, timing is recorded for completeness.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.fxp_matmul import fxp_matmul
from repro.kernels.pofx_decode import pofx_decode
from repro.kernels.pofx_matmul import pofx_matmul
from repro.kernels.ref import fxp_matmul_ref, pofx_decode_ref, pofx_matmul_ref

from .common import wall_time, write_csv


def run(smoke: bool = False):
    rng = np.random.default_rng(0)
    rows = []
    # decode kernel sweep (smoke keeps one ragged + one aligned shape —
    # the tail-tile masking is the path that rots)
    dec_shapes = ((128, 256), (257, 130)) if smoke \
        else ((128, 256), (257, 130), (512, 512))
    for (r, c) in dec_shapes:
        for N, ES in (((8, 2),) if smoke else ((8, 2), (6, 1))):
            codes = jnp.asarray(rng.integers(0, 1 << (N - 1), (r, c)),
                                jnp.int32)
            out = pofx_decode(codes, N, ES, 8, block=(128, 128), interpret=True)
            ref = pofx_decode_ref(codes, N, ES, 8)
            ok = bool(jnp.all(out == ref))
            rows.append({"kernel": "pofx_decode", "shape": f"{r}x{c}",
                         "cfg": f"({N},{ES})", "exact": ok,
                         "us": wall_time(lambda: pofx_decode(
                             codes, N, ES, 8, block=(128, 128),
                             interpret=True), reps=2) * 1e6})
            assert ok
    # fused matmul sweep
    for (m, k, n) in (((64, 128, 96),) if smoke
                      else ((64, 128, 96), (130, 257, 66))):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 128, (k, n)), jnp.int32)
        scale = jnp.asarray(rng.uniform(0.5, 2.0, (n,)), jnp.float32)
        for mode in ("bitlevel", "table"):
            got = pofx_matmul(x, codes, scale, 8, 2, 8, blocks=(64, 64, 64),
                              decode_mode=mode, interpret=True)
            ref = pofx_matmul_ref(x, codes, scale, 8, 2, 8)
            err = float(jnp.max(jnp.abs(got - ref)))
            rows.append({"kernel": f"pofx_matmul[{mode}]",
                         "shape": f"{m}x{k}x{n}", "cfg": "(8,2)",
                         "exact": err < 1e-3, "us": err})
            assert err < 1e-3, (mode, err)
    # int8 MAC
    a = jnp.asarray(rng.integers(-127, 127, (96, 160)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 127, (160, 64)), jnp.int8)
    got = fxp_matmul(a, b, blocks=(64, 64, 64), interpret=True)
    ok = bool(jnp.all(got == fxp_matmul_ref(a, b)))
    rows.append({"kernel": "fxp_matmul", "shape": "96x160x64", "cfg": "int8",
                 "exact": ok, "us": 0.0})
    assert ok
    write_csv("kernels", rows)
    return rows, {"all_exact": all(r["exact"] for r in rows)}
