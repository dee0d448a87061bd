"""Pallas TPU kernel: fused PoFx decode + matmul — the Move&Store datapath.

This is the paper's PoFx(Move & Store) accelerator (Fig. 20, design 3) mapped
onto the TPU memory hierarchy:

    HBM:   W stored as uint8 normalized-posit codes  ((N-1)/16 of bf16 bytes)
    VMEM:  per-(k,j) tile decoded on the VPU, then
    MXU:   dot against the activation tile in the activations' dtype (bf16
           or f32), f32 accumulation in a VMEM scratch accumulator across
           the k grid dimension.

Decode modes:
  "table"    — (default) the 2^(N-1)-entry PoFx table (``pofx_norm_lut``,
               built by Algorithm 1) held in one 128-lane row; each
               128-lane slab of the code tile is a lane gather from it.
               Needs 2^(N-1) <= 128, i.e. N <= 8; wider formats take
               "bitlevel".
  "bitlevel" — Algorithm 1 stages as lane-wise int32 ops on every weight
               (the faithful port, the oracle's twin).

Both give the same FxP(M, M-1) value for every code. For M <= 8 each value
/ 2^(M-1) is exact in bf16, so a bf16 x meets the MXU against a bf16 weight
tile with every product exact; any other x, or a wider M, runs in f32.

Weight HBM traffic per step drops to (N-1 bits)/weight vs 16 (bf16) — this is
the paper's storage/communication reduction re-expressed as the memory-
roofline term that dominates TPU decode workloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.pofx import pofx_norm_lut
from . import default_blocks, vmem_scratch
from .ref import decode_norm_to_fxp

__all__ = ["pofx_matmul"]

_DECODE_MODES = ("table", "bitlevel")
_LANES = 128  # one vreg row: the table's width and the gather's slab width


def _kernel(*refs, N, ES, M, nk, table):
    if table:
        x_ref, w_ref, s_ref, t_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # |FxP(M, M-1)| < 2^7 for M <= 8, so the weight tile is exact in bf16.
    dt = jnp.bfloat16 if x_ref.dtype == jnp.bfloat16 and M <= 8 else jnp.float32
    x = x_ref[...].astype(dt)
    bk, bn = w_ref.shape
    if table:
        # A gather indexes within one 128-lane row, so decode slab by slab.
        tbl = jnp.broadcast_to(t_ref[...], (bk, _LANES))
        for j in range(0, bn, _LANES):
            sl = slice(j, min(j + _LANES, bn))
            codes = w_ref[:, sl].astype(jnp.int32)
            width = codes.shape[1]
            if width < _LANES:  # a narrow last slab: gather a full row
                codes = jnp.pad(codes, ((0, 0), (0, _LANES - width)))
            w = jnp.take_along_axis(tbl, codes, axis=1,
                                    mode="promise_in_bounds")[:, :width]
            acc_ref[:, sl] += jnp.dot(x, w.astype(dt),
                                      preferred_element_type=jnp.float32)
    else:
        fxp = decode_norm_to_fxp(w_ref[...].astype(jnp.int32), N, ES, M)
        w = (fxp.astype(jnp.float32) * (1.0 / (1 << (M - 1)))).astype(dt)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _table(N: int, ES: int, M: int) -> jax.Array:
    """The decoded values of every code, / 2^(M-1), padded to 128 lanes."""
    vals = pofx_norm_lut(N, ES, M).astype(np.float32) / (1 << (M - 1))
    return jnp.asarray(np.pad(vals, (0, _LANES - vals.size)).reshape(1, _LANES))


@functools.partial(jax.jit, static_argnames=("N", "ES", "M", "blocks", "decode_mode",
                                             "interpret", "out_dtype"))
def pofx_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array,
                N: int, ES: int, M: int = 8, blocks=None,
                decode_mode: str = "table", interpret: bool | None = None,
                out_dtype=jnp.float32) -> jax.Array:
    """x:(m,k) @ decode(codes:(k,n)) * scale:(n,) -> (m,n)."""
    if decode_mode not in _DECODE_MODES:
        raise ValueError(f"decode_mode {decode_mode!r} not in {_DECODE_MODES}")
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
    table = decode_mode == "table" and (1 << (N - 1)) <= _LANES
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
    ]
    operands = [xp, cp, sp]
    if table:
        in_specs.append(pl.BlockSpec((1, _LANES), lambda i, j, k: (0, 0)))
        operands.append(_table(N, ES, M))
    out = pl.pallas_call(
        functools.partial(_kernel, N=N, ES=ES, M=M, nk=grid[2], table=table),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], cp.shape[1]), out_dtype),
        scratch_shapes=[vmem_scratch((bm, bn))],
        interpret=interpret,
        name="pofx_matmul",
    )(*operands)
    return out[:m, :n]
