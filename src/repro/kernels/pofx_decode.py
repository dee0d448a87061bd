"""Pallas TPU kernel: PoFx decode — normalized posit codes -> FxP int8.

The TPU port of the paper's PoFx converter (Algorithm 1). On FPGA the
converter is an LZD + barrel shifter; on TPU the same stages become lane-wise
int32 bit operations on the VPU — every lane decodes one weight, no
data-dependent control flow. HBM holds uint8 codes ((N-1) <= 8 bits each),
VMEM tiles are decoded in place; the output int8 feeds the MXU (or is widened
to bf16 by the fused kernel).

BlockSpec: 2D (block_r, block_c) tiles in VMEM. uint8 tiles are (32, 128)
packed on TPU; we keep block shapes multiples of (32, 128) for lane/sublane
alignment with int8/uint8 layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import decode_norm_to_fxp

__all__ = ["pofx_decode"]

DEFAULT_BLOCK = (256, 512)


def _decode_kernel(codes_ref, out_ref, *, N: int, ES: int, M: int):
    codes = codes_ref[...].astype(jnp.int32)
    out_ref[...] = decode_norm_to_fxp(codes, N, ES, M).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("N", "ES", "M", "block", "interpret"))
def pofx_decode(codes: jax.Array, N: int, ES: int, M: int = 8,
                block=DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    """Decode a 2D array of normalized posit codes to FxP int8 codes."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    r, c = codes.shape
    br, bc = min(block[0], r), min(block[1], c)
    pr, pc = (-r) % br, (-c) % bc
    padded = jnp.pad(codes, ((0, pr), (0, pc)))
    grid = (padded.shape[0] // br, padded.shape[1] // bc)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, N=N, ES=ES, M=M),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(padded.shape, jnp.int8),
        interpret=interpret,
        name="pofx_decode",
    )(padded)
    return out[:r, :c]
