"""Pallas TPU kernel: fused quantized-KV-cache flash-decode attention.

The paper's Move&Store datapath applied to the *decode KV cache* — the term
that dominates HBM traffic per decoded token at long context (weights are
amortized over the batch; the cache is re-read per token per sequence):

    HBM:   K/V stored as byte-wide quantization codes (int8 FxP two's
           complement, or uint8 normalized-posit) + a tiny static
           per-head-dim-channel scale — (8 or fewer)/16 of the bf16 bytes
    VMEM:  each (block_s, Dh) code tile is dequantized on the VPU right
           after the DMA lands (fxp: one int->float multiply; pofx: the
           bit-level Algorithm-1 stages, same as pofx_matmul)
    MXU:   online-softmax flash decode against the dequantized tile, f32
           scratch accumulators (m, l, acc) carried across the S grid axis

Full-precision K/V never round-trips through HBM: the cache is written as
codes (``nn.attention`` quantizes on write) and only ever expands inside
VMEM. The XLA fallback (quantize-on-write, dequantize-on-read via
``core.quantizers.kv_dequantize`` + plain ``decode_attention``) computes the
same math out-of-place and is the oracle this kernel is tested against.

``pos`` is per-slot (B,): entries at or beyond a slot's valid length mask to
-inf exactly like the XLA path, so ragged continuous-batching slots and
zero-padded tail tiles are safe (code 0 decodes to value 0 and is masked
anyway — see tests/test_kernels.py::test_pad_code_zero_decodes_to_zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fxp import fxp_dequantize
from repro.core.quantizers import QuantSpec
from . import vmem_scratch
from .ref import decode_norm_to_fxp

__all__ = ["kv_flash_decode"]

NEG_INF = -1e30

# KV-sequence block length per backend (lane-dim tiles are full head_dim).
DEFAULT_BLOCK_S = {"tpu": 512, "cpu": 128, "gpu": 256}


def _dequant_tile(codes, spec: QuantSpec, scale_row):
    """codes (bs, Dh) int -> f32 values; scale_row (1, Dh) broadcasts."""
    c = codes.astype(jnp.int32)
    if spec.kind == "fxp":
        v = fxp_dequantize(c, spec.F)
    else:  # pofx: bit-level Algorithm 1 on the VPU, then FxP(M, M-1) value
        v = fxp_dequantize(decode_norm_to_fxp(c, spec.N, spec.ES, spec.M),
                           spec.M - 1)
    return v * scale_row


def _kernel(pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, spec: QuantSpec, bs: int, ns: int,
            scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (R, Dh)
    k = _dequant_tile(kc_ref[0, 0], spec, ks_ref[0, 0])     # (bs, Dh)
    sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (R,bs)
    idx = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    sc = jnp.where(idx < pos_ref[b], sc, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]                 # (R, 1)
    m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)                                 # (R, bs)
    corr = jnp.exp(m_prev - m_new)
    v = _dequant_tile(vc_ref[0, 0], spec, vs_ref[0, 0])     # (bs, Dh)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "block_s", "interpret",
                                             "out_dtype"))
def kv_flash_decode(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                    v_codes: jax.Array, v_scale: jax.Array, pos: jax.Array,
                    spec: QuantSpec, *, block_s: int | None = None,
                    interpret: bool | None = None,
                    out_dtype=jnp.float32) -> jax.Array:
    """One-token attention against a quantized heads-major cache.

    q:        (B, G, R, Dh) float queries (R = q heads per kv group)
    k_codes:  (B, G, S, Dh) int8/uint8 cache codes (``kv_code_dtype``)
    k_scale:  (B, G, 1, Dh) f32 static per-head-dim-channel normalizer
    v_codes / v_scale: same layouts for V
    pos:      scalar or (B,) valid-prefix lengths (mask: arange(S) < pos)

    Returns (B, G, R, Dh) in ``out_dtype``. Grid is (B, G, S/block_s) with
    the S axis innermost; the online-softmax state lives in VMEM scratch.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, G, R, Dh = q.shape
    S = k_codes.shape[2]
    if v_codes.shape != k_codes.shape:
        raise ValueError(
            f"k/v code shape mismatch: {k_codes.shape} vs {v_codes.shape}")
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc.shape[-3:] != (G, 1, Dh):
            # must raise: the (1, Dh) BlockSpec would silently read row 0
            # of a mis-shaped scale while the XLA fallback broadcasts it
            raise ValueError(
                f"kv {name} must be per-head-dim-channel "
                f"(..., {G}, 1, {Dh}); got {sc.shape}")
    if block_s is None:
        block_s = DEFAULT_BLOCK_S.get(jax.default_backend(),
                                      DEFAULT_BLOCK_S["tpu"])
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad and interpret:
        # interpret mode only: pallas's CPU emulation needs block-divisible
        # dims. On TPU the final partial tile is DMA'd as-is (OOB lanes are
        # undefined but finite once dequantized, and idx >= pos masks them
        # to -inf) — explicitly padding there would re-copy the full code
        # caches in HBM per step per layer, eroding the bandwidth win.
        k_codes = jnp.pad(k_codes, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_codes = jnp.pad(v_codes, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ns = (S + pad) // bs
    pos1 = jnp.broadcast_to(jnp.reshape(pos, (-1,)), (B,)).astype(jnp.int32)
    # pos rides in SMEM as a scalar-prefetch operand: a (1, 1) VMEM block of
    # a (B, 1) array breaks the TPU's (8, 128) block-tiling rule.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, G, ns),
        in_specs=[
            pl.BlockSpec((1, 1, R, Dh), lambda b, g, s, pos: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, g, s, pos: (b, g, s, 0)),
            pl.BlockSpec((1, 1, 1, Dh), lambda b, g, s, pos: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, Dh), lambda b, g, s, pos: (b, g, s, 0)),
            pl.BlockSpec((1, 1, 1, Dh), lambda b, g, s, pos: (b, g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R, Dh),
                               lambda b, g, s, pos: (b, g, 0, 0)),
        scratch_shapes=[vmem_scratch((R, 1)), vmem_scratch((R, 1)),
                        vmem_scratch((R, Dh))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, bs=bs, ns=ns,
                          scale=Dh ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, R, Dh), out_dtype),
        interpret=interpret,
        name="kv_flash_decode",
    )(pos1, q.astype(jnp.float32), k_codes, k_scale.astype(jnp.float32),
      v_codes, v_scale.astype(jnp.float32))
    return out
