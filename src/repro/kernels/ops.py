"""Public jit'd entry points for the kernels, with automatic dispatch.

``quant_matmul`` is what the model layers call: given activations and a
QuantizedTensor weight it picks the right datapath —

  pofx   + use_kernel   -> fused Pallas decode+matmul (Move & Store)
  pofx   + no kernel    -> LUT dequantize + XLA matmul (Move; decode at load)
  fxp    + int8 acts    -> int8 MXU MAC (fxp_matmul)
  others                -> dequantize + XLA matmul

``use_kernel`` is a plain bool set by the model (``build_model(...,
use_kernel=True)``). Kernels compile to Mosaic on TPU and run in interpret
mode on CPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantizers import QuantizedTensor, dequantize, fxp_view
from .fxp_matmul import fxp_matmul
from .pofx_decode import pofx_decode
from .pofx_matmul import pofx_matmul

__all__ = ["quant_matmul", "out_channel_scale", "pofx_decode", "pofx_matmul",
           "fxp_matmul"]


def out_channel_scale(scale: jax.Array, codes_shape) -> jax.Array:
    """Validate a QuantizedTensor scale layout and collapse it to (1, n).

    Every quantized-matmul datapath folds the normalizer in *after* the
    contraction — y = (x @ decode(codes)) * scale — which is only sound
    when the scale is constant along the contraction axis (codes axis 0):
    per-output-channel, per-tensor, or any broadcast shape that never
    covers axis 0. A scale that varies along the contraction axis would
    need the rescale inside the MAC loop, which no kernel implements, so
    it raises instead of silently keeping row 0 of the flattened scale
    (the old corrupting behavior). NumPy broadcasting aligns trailing
    dims, so axis 0 is covered iff scale.ndim == codes.ndim.
    """
    sshape = tuple(getattr(scale, "shape", ()))
    if len(sshape) > len(codes_shape):
        raise ValueError(
            f"scale rank {len(sshape)} exceeds codes rank {len(codes_shape)} "
            f"(scale {sshape} vs codes {tuple(codes_shape)})")
    if len(sshape) == len(codes_shape) and sshape[0] != 1:
        raise ValueError(
            f"unsupported scale layout {sshape} for codes "
            f"{tuple(codes_shape)}: the scale varies along the contraction "
            "axis (codes axis 0); quantized matmuls apply the normalizer "
            "after the contraction, so only per-output-channel or "
            "per-tensor scales are representable")
    try:
        out = jnp.broadcast_to(scale, (1, *codes_shape[1:]))
    except ValueError as e:
        raise ValueError(
            f"scale shape {sshape} does not broadcast against the output "
            f"dims of codes {tuple(codes_shape)}: {e}") from None
    return out.reshape(1, -1)


def quant_matmul(x: jax.Array, w: QuantizedTensor, *,
                 use_kernel: bool = False,
                 out_dtype=None) -> jax.Array:
    """x @ dequant(w); x: (..., k), w codes: (k, n).

    The kernel paths require an out-channel scale layout (see
    ``out_channel_scale``); the dequantize fallback is mathematically
    general and stays permissive.
    """
    out_dtype = out_dtype or x.dtype
    spec = w.spec
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if spec.kind == "pofx" and use_kernel:
        scale = out_channel_scale(w.scale, w.codes.shape).reshape(-1)
        y = pofx_matmul(x2, w.codes, scale, spec.N, spec.ES, spec.M)
        return y.reshape(*lead, -1).astype(out_dtype)
    if spec.kind == "fxp" and use_kernel:
        codes, rescale = fxp_view(w)
        rescale = out_channel_scale(rescale, w.codes.shape)
        # int8 activations: per-tensor symmetric quantization of x.
        xmax = jnp.maximum(jnp.max(jnp.abs(x2)), 1e-6)
        xq = jnp.clip(jnp.round(x2 / xmax * 127.0), -127, 127).astype(jnp.int8)
        acc = fxp_matmul(xq, codes)
        y = acc.astype(jnp.float32) * (xmax / 127.0) * rescale
        return y.reshape(*lead, -1).astype(out_dtype)
    wv = dequantize(w, jnp.bfloat16 if out_dtype == jnp.bfloat16 else jnp.float32)
    y = jnp.dot(x2.astype(wv.dtype), wv, preferred_element_type=jnp.float32)
    return y.reshape(*lead, -1).astype(out_dtype)
