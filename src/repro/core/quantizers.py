"""Quantizer registry — ExPAN(N)D storage/compute formats as a pytree type.

A ``QuantSpec`` names one point of the paper's design space:

  kind = "fp32" | "bf16"        passthrough baselines
       | "fxp"                  FxP(M, F) linear quantization (paper baseline)
       | "posit"                Posit(N, ES) storage, full-precision compute
                                (the Posit-only comparator of Table 5)
       | "pofx"                 **the paper's format**: normalized Posit(N-1,
                                ES) storage, FxP(M, F=M-1) compute after PoFx

  path (pofx only) = "direct"   FP32 -> Posit   -> FxP   (Table 5 "Posit_FxP")
                   | "via_fxp"  FP32 -> FxP -> Posit -> FxP ("FxP_Posit_FxP")

  scale_mode: normalizer bringing weights into [-1, 1] (see core.fxp);
  "none" reproduces the paper's already-normalized assumption.

``QuantizedTensor`` is a registered pytree (codes + scale are leaves, spec is
static) so quantized params flow through jit/pjit/scan and checkpointing.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import fxp as _fxp
from . import normalized_posit as _np_
from . import posit as _posit
from .pofx import pofx_normalized

__all__ = ["QuantSpec", "QuantizedTensor", "quantize", "dequantize",
           "storage_bits", "validate_kv_spec", "kv_code_dtype", "kv_quantize",
           "kv_dequantize"]

_KINDS = ("fp32", "bf16", "fxp", "posit", "pofx")


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    kind: str = "bf16"
    N: int = 8            # posit total bit length (stored bits = N-1 for pofx)
    ES: int = 2
    M: int = 8            # FxP total bits
    F: int = 7            # FxP fraction bits (pofx forces F = M-1)
    path: str = "via_fxp"  # pofx quantization path
    scale_mode: str = "channel_pow2"
    rounding: str = "trunc"

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown quant kind {self.kind!r}")

    @property
    def stored_bits(self) -> int:
        """Bits per stored weight (the paper's storage accounting)."""
        if self.kind == "fp32":
            return 32
        if self.kind == "bf16":
            return 16
        if self.kind == "fxp":
            return self.M
        if self.kind == "posit":
            return self.N
        return self.N - 1  # pofx: normalized posit stores N-1 bits

    def code_dtype(self):
        b = self.stored_bits
        if b <= 8:
            return jnp.uint8
        if b <= 15:
            return jnp.int16
        return jnp.int32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    codes: jax.Array          # packed per-weight codes (or raw floats)
    scale: jax.Array          # normalizer, broadcastable against codes
    spec: QuantSpec

    @property
    def shape(self):
        return self.codes.shape

    @property
    def ndim(self):
        return self.codes.ndim

    def tree_flatten(self):
        return (self.codes, self.scale), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], children[1], spec)

    def dequantize(self, dtype=jnp.bfloat16):
        return dequantize(self, dtype)


def _as_f32(x):
    return jnp.asarray(x, dtype=jnp.float32)


# Leaves larger than this many elements encode a block of rows at a time:
# the encoder's full-size int32/f32 temporaries would otherwise cost several
# times the leaf (about 3.5 GB for the 0.26 GB of yi-9b embedding codes).
_ENCODE_BLOCK = 1 << 22


def _encode(wn, spec: QuantSpec) -> jax.Array:
    """Normalized values (in [-1, 1]) -> storage codes, elementwise."""
    if spec.kind == "fxp":
        codes = _fxp.fxp_quantize(wn, spec.M, spec.F)
        return codes.astype(jnp.int8 if spec.M <= 8 else jnp.int32)
    if spec.kind == "posit":
        codes = _posit.posit_encode(wn, spec.N, spec.ES)
        return codes.astype(spec.code_dtype())
    # pofx: optionally pre-round through the FxP grid (Table 5's good path),
    # then encode onto the normalized posit lattice.
    if spec.path == "via_fxp":
        wn = _fxp.fxp_dequantize(_fxp.fxp_quantize(wn, spec.M, spec.M - 1), spec.M - 1)
    return _np_.norm_encode(wn, spec.N, spec.ES).astype(spec.code_dtype())


def quantize(w, spec: QuantSpec, axis: Optional[int] = None) -> QuantizedTensor:
    """Quantize a float array into the storage format named by ``spec``."""
    w = jnp.asarray(w)
    if spec.kind in ("fp32", "bf16"):
        dt = jnp.float32 if spec.kind == "fp32" else jnp.bfloat16
        one = jnp.ones((1,) * max(w.ndim, 1), jnp.float32)
        return QuantizedTensor(_as_f32(w).astype(dt), one, spec)
    if axis is None and spec.scale_mode.startswith("channel"):
        axis = -1  # convention: last axis is the output-channel axis
    scale = _fxp.compute_scale(_as_f32(w), spec.scale_mode, axis)
    if w.ndim < 2 or w.size <= _ENCODE_BLOCK or scale.shape[0] != 1:
        return QuantizedTensor(_encode(_as_f32(w) / scale, spec), scale, spec)
    rows = max(1, _ENCODE_BLOCK // (w.size // w.shape[0]))
    codes = jax.lax.map(lambda row: _encode(_as_f32(row) / scale[0], spec),
                        w, batch_size=rows)
    return QuantizedTensor(codes, scale, spec)


def _pofx_fxp_codes(codes, spec: QuantSpec) -> jax.Array:
    """Stored pofx codes -> FxP(M, M-1) codes by the bit-level algorithm,
    elementwise: the values of ``pofx_norm_lut`` without a table lookup,
    which on the TPU is a gather per code (seconds per full-model decode)."""
    return pofx_normalized(codes.astype(jnp.int32), spec.N, spec.ES, spec.M,
                           spec.rounding)[0]


def _codes_to_values(codes, spec: QuantSpec) -> jax.Array:
    """Integer codes -> unscaled float values through the FxP datapath.

    The ONE copy of the hardware decode both the weight path (dequantize)
    and the KV-cache path (kv_dequantize, and tile-wise the flash-decode
    kernel) must agree on bit-for-bit: fxp is a two's-complement shift;
    pofx goes stored posit -> FxP(M, M-1) (``_pofx_fxp_codes``) -> value.
    """
    if spec.kind == "fxp":
        return _fxp.fxp_dequantize(codes, spec.F)
    if spec.kind == "pofx":
        return _fxp.fxp_dequantize(_pofx_fxp_codes(codes, spec), spec.M - 1)
    raise ValueError(f"no FxP decode path for kind {spec.kind!r}")


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jax.Array:
    """Recover float values as the *hardware* would see them.

    pofx goes through the bit-level PoFx conversion: stored posit -> FxP(M,
    M-1) two's-complement -> value * scale.  This is the datapath of Fig. 7.
    """
    spec = qt.spec
    if spec.kind in ("fp32", "bf16"):
        return qt.codes.astype(dtype)
    if spec.kind == "posit":
        v = _posit.posit_decode(qt.codes, spec.N, spec.ES)
    else:  # fxp / pofx
        v = _codes_to_values(qt.codes, spec)
    return (v * qt.scale).astype(dtype)


def fxp_view(qt: QuantizedTensor):
    """(int8 codes, float rescale) pair for the int8 MXU MAC path."""
    spec = qt.spec
    if spec.kind == "fxp":
        return qt.codes.astype(jnp.int8), qt.scale * (1.0 / (1 << spec.F))
    if spec.kind == "pofx":
        codes = _pofx_fxp_codes(qt.codes, spec).astype(jnp.int8)
        return codes, qt.scale * (1.0 / (1 << (spec.M - 1)))
    raise ValueError(f"no FxP view for kind {spec.kind!r}")


# ---------------------------------------------------------------------------
# Quantized KV cache — elementwise code path for 4D (B, G, S, Dh) tensors
# ---------------------------------------------------------------------------
#
# The decode KV cache stores quantization *codes* (one byte-wide lane per
# element, streamed from HBM by kernels.kv_flash_decode) next to a STATIC
# per-head-dim-channel normalizer scale leaf. The scale must not depend on
# the data written so far: quantize-on-write is lossy, and the engine's
# evict -> re-prefill resume is bit-identical only because re-quantizing the
# same float always yields the same code — a running (data-dependent) scale
# would re-scale history and corrupt resumed streams (DESIGN.md §8).
# Unlike the weight path there is no QuantizedTensor wrapper here: cache
# leaves must flatten 1:1 against ``LM.cache_logical`` for the engine's slot
# scatter, so codes and scale travel as sibling dict leaves.


def validate_kv_spec(spec: Optional[QuantSpec]) -> Optional[QuantSpec]:
    """Check a spec is usable as a KV-cache format; returns it (or None).

    bf16/fp32 mean "unquantized cache" and normalize to None. Quantized
    caches require byte-wide codes (stored_bits <= 8) of a kind with an FxP
    decode path the kernel implements: fxp or pofx.
    """
    if spec is None or spec.kind in ("bf16", "fp32"):
        return None
    if spec.kind not in ("fxp", "pofx"):
        raise ValueError(
            f"kv cache format must be fxp or pofx (got {spec.kind!r}): the "
            "flash-decode kernel dequantizes through the FxP datapath")
    if spec.stored_bits > 8:
        raise ValueError(
            f"kv cache codes must be byte-wide (stored_bits <= 8, got "
            f"{spec.stored_bits}): the cache streams uint8/int8 code tiles")
    if spec.kind == "pofx" and spec.rounding != "trunc":
        raise ValueError(
            f"kv cache pofx specs must use trunc rounding (got "
            f"{spec.rounding!r}): the flash-decode kernel's bit-level VPU "
            "decode truncates, and the XLA fallback must match it "
            "code-for-code")
    return spec


def kv_code_dtype(spec: QuantSpec):
    """Cache code dtype: int8 two's-complement for fxp, uint8 posit codes."""
    return jnp.int8 if spec.kind == "fxp" else jnp.uint8


def kv_quantize(x, spec: QuantSpec, scale) -> jax.Array:
    """Quantize K/V values into cache codes. Elementwise over any shape.

    ``scale`` is the static per-head-dim-channel normalizer leaf (typically
    (B, G, 1, Dh), broadcastable against ``x``); values outside the format's
    range after normalization saturate, exactly as the weight path does.
    """
    wn = _as_f32(x) / scale
    if spec.kind == "fxp":
        return _fxp.fxp_quantize(wn, spec.M, spec.F).astype(jnp.int8)
    if spec.kind != "pofx":
        raise ValueError(f"no kv code path for kind {spec.kind!r}")
    if spec.path == "via_fxp":
        wn = _fxp.fxp_dequantize(_fxp.fxp_quantize(wn, spec.M, spec.M - 1),
                                 spec.M - 1)
    return _np_.norm_encode(wn, spec.N, spec.ES).astype(jnp.uint8)


def kv_dequantize(codes, spec: QuantSpec, scale, dtype=jnp.float32) -> jax.Array:
    """Recover K/V values from cache codes (the XLA fallback / oracle path).

    This is the same math ``kernels.kv_flash_decode`` runs tile-wise in
    VMEM: codes -> FxP two's complement -> value * scale. It shares
    ``_codes_to_values`` with the weight path so the decode the
    kernel-vs-fallback and evict-resume contracts depend on has one copy.
    """
    return (_codes_to_values(codes, spec) * scale).astype(dtype)


def storage_bits(qt: QuantizedTensor) -> int:
    """Total stored parameter bits (codes bit-packed + fp32 scales)."""
    n = int(np.prod(qt.codes.shape)) if qt.codes.ndim else 1
    scale_n = int(np.prod(qt.scale.shape)) if qt.scale.ndim else 1
    if qt.spec.kind in ("fp32", "bf16"):
        return n * qt.spec.stored_bits
    return n * qt.spec.stored_bits + scale_n * 32
