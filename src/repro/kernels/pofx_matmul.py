"""Pallas TPU kernel: fused PoFx decode + matmul — the Move&Store datapath.

This is the paper's PoFx(Move & Store) accelerator (Fig. 20, design 3) mapped
onto the TPU memory hierarchy:

    HBM:   W stored as uint8 normalized-posit codes  ((N-1)/16 of bf16 bytes)
    VMEM:  per-(k,j) tile decoded on the VPU (bit-level Algorithm 1), then
    MXU:   bf16/f32 dot against the activation tile, f32 accumulation in a
           VMEM scratch accumulator across the k grid dimension.

Decode modes:
  "bitlevel" — Algorithm 1 stages as lane-wise int32 ops (faithful port);
  "onehot"   — 2^(N-1)-entry LUT realized as one-hot @ table matmul, i.e. the
               decode itself runs on the MXU (TPU-idiomatic alternative; the
               §Perf log compares both).

Weight HBM traffic per step drops to (N-1 bits)/weight vs 16 (bf16) — this is
the paper's storage/communication reduction re-expressed as the memory-
roofline term that dominates TPU decode workloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pofx import pofx_norm_lut
from . import default_blocks, vmem_scratch
from .ref import decode_norm_to_fxp

__all__ = ["pofx_matmul"]


def _kernel(x_ref, w_ref, s_ref, lut_ref, o_ref, acc_ref, *, N, ES, M, nk, decode_mode):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = w_ref[...].astype(jnp.int32)
    inv = 1.0 / (1 << (M - 1))
    if decode_mode == "onehot":
        # One-hot matmul against the LUT: decode on the MXU. codes tile
        # (bk, bn) -> one-hot against the 2^(N-1)-entry value table.
        depth = 1 << (N - 1)
        oh = (codes[..., None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, depth), 2))
        vals = lut_ref[...].astype(jnp.float32) * inv  # (1, depth)
        w = jnp.sum(oh.astype(jnp.float32) * vals[0], axis=-1)
    else:
        fxp = decode_norm_to_fxp(codes, N, ES, M)
        w = fxp.astype(jnp.float32) * inv
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("N", "ES", "M", "blocks", "decode_mode",
                                             "interpret", "out_dtype"))
def pofx_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array,
                N: int, ES: int, M: int = 8, blocks=None,
                decode_mode: str = "bitlevel", interpret: bool | None = None,
                out_dtype=jnp.float32) -> jax.Array:
    """x:(m,k) @ decode(codes:(k,n)) * scale:(n,) -> (m,n)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if blocks is None:
        blocks = default_blocks()
    m, kdim = x.shape
    k2, n = codes.shape
    if kdim != k2:
        # A real error, not a bare assert: this check guards the public
        # kernel entry and must survive `python -O`.
        raise ValueError(
            f"contraction mismatch: x {x.shape} @ codes {codes.shape}")
    bm, bn, bk = (min(blocks[0], m), min(blocks[1], n), min(blocks[2], kdim))
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    cp = jnp.pad(codes, ((0, pk), (0, pn)))  # code 0 decodes to 0 -> safe pad
    sp = jnp.pad(jnp.reshape(scale, (1, -1)).astype(jnp.float32), ((0, 0), (0, pn)))
    grid = (xp.shape[0] // bm, cp.shape[1] // bn, xp.shape[1] // bk)
    depth = 1 << (N - 1)
    lut = jnp.asarray(pofx_norm_lut(N, ES, M), jnp.int32).reshape(1, depth)
    out = pl.pallas_call(
        functools.partial(_kernel, N=N, ES=ES, M=M, nk=grid[2],
                          decode_mode=decode_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, depth), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], cp.shape[1]), out_dtype),
        scratch_shapes=[vmem_scratch((bm, bn))],
        interpret=interpret,
        name="pofx_matmul",
    )(xp, cp, sp, lut)
    return out[:m, :n]
