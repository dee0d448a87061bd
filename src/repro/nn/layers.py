"""Primitive layers: quantization-aware matmul, RMSNorm, rotary, MLPs.

Weights flow through every layer either as plain float arrays (training) or
as ``QuantizedTensor`` (post-training-quantized serving, the paper's mode).
``matmul_param`` dispatches: quantized weights go through the PoFx/FxP
datapath (XLA LUT path inside big jit graphs; Pallas kernels are validated
separately and selectable via use_kernel for eager serving).
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core.quantizers import QuantizedTensor, dequantize
from repro.kernels.ops import out_channel_scale, quant_matmul

Param = Union[jax.Array, QuantizedTensor]


def param_value(w: Param, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize (or cast) a parameter for direct elementwise use."""
    if isinstance(w, QuantizedTensor):
        return dequantize(w, dtype)
    return w.astype(dtype)


def matmul_param(x: jax.Array, w: Param, *, out_shape=None,
                 use_kernel: bool = False) -> jax.Array:
    """x:(..., k) @ w:(k, ...) with quantized-weight dispatch.

    ``w`` may have multiple output dims (e.g. (d_model, H, Dh)); pass
    ``out_shape`` to reshape the flattened output. Quantized weights must
    carry an out-channel scale layout — a scale varying along the
    contraction axis (codes axis 0) raises (see
    ``repro.kernels.ops.out_channel_scale``; DESIGN.md §2).
    """
    if isinstance(w, QuantizedTensor):
        k = w.codes.shape[0]
        codes2 = w.codes.reshape(k, -1)
        scale2 = out_channel_scale(w.scale, w.codes.shape)
        w2 = QuantizedTensor(codes2, scale2, w.spec)
        y = quant_matmul(x, w2, use_kernel=use_kernel)
        tail = w.codes.shape[1:]
    else:
        k = w.shape[0]
        y = jnp.dot(x, w.reshape(k, -1).astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
        tail = w.shape[1:]
    return y.reshape(*x.shape[:-1], *(out_shape or tail))


def rmsnorm(x: jax.Array, w: Param, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * param_value(w, jnp.float32)).astype(dt)


def rotary_cos_sin(positions: jax.Array, d_head: int, theta: float):
    """cos/sin tables for the given positions: (..., d_head//2)."""
    half = d_head // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, Dh); cos/sin: (B, S, Dh//2) -> broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "gelu_plain"):
        return jax.nn.gelu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def is_gated(act: str) -> bool:
    return act in ("silu", "gelu")


def mlp_forward(p: dict, x: jax.Array, act: str, ctx, use_kernel: bool = False) -> jax.Array:
    """Gated (silu/gelu: wg,wu,wo) or plain (relu2/gelu_plain: wi,wo) MLP."""
    fn = activation(act)
    if is_gated(act):
        g = matmul_param(x, p["wg"], use_kernel=use_kernel)
        u = matmul_param(x, p["wu"], use_kernel=use_kernel)
        h = fn(g) * u
    else:
        h = fn(matmul_param(x, p["wi"], use_kernel=use_kernel))
    h = ctx.constrain(h, "batch", "seq_attn", "mlp")
    # down-proj is row-sharded under manual TP (contraction over the local
    # d_ff shard): the block's one MLP collective (DESIGN.md §9).
    return ctx.psum(matmul_param(h, p["wo"], use_kernel=use_kernel))


def dense_init(key, in_dim: int, out_dims, scale: Optional[float] = None,
               dtype=jnp.float32) -> jax.Array:
    out_dims = (out_dims,) if isinstance(out_dims, int) else tuple(out_dims)
    if scale is None:
        scale = in_dim ** -0.5
    # The barrier keeps XLA from folding ``scale`` into the normal's own
    # constants inside a larger jit: a jitted init then draws the same bits
    # as an eager one, which LM.init_quantized relies on.
    w = jax.lax.optimization_barrier(
        jax.random.normal(key, (in_dim, *out_dims), dtype=jnp.float32))
    return (w * scale).astype(dtype)


def mlp_init(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32) -> dict:
    if is_gated(act):
        ks = jax.random.split(key, 3)
        return {
            "wg": dense_init(ks[0], d_model, d_ff, dtype=dtype),
            "wu": dense_init(ks[1], d_model, d_ff, dtype=dtype),
            "wo": dense_init(ks[2], d_ff, d_model, dtype=dtype),
        }
    ks = jax.random.split(key, 2)
    return {
        "wi": dense_init(ks[0], d_model, d_ff, dtype=dtype),
        "wo": dense_init(ks[1], d_ff, d_model, dtype=dtype),
    }


def mlp_logical(act: str) -> dict:
    if is_gated(act):
        return {"wg": ("p_embed", "mlp"), "wu": ("p_embed", "mlp"),
                "wo": ("mlp", "p_embed")}
    return {"wi": ("p_embed", "mlp"), "wo": ("mlp", "p_embed")}
