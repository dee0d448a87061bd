"""Model stacks for every assigned family, built scan-over-layers.

One ``block_*`` triple (init / logical / forward) per block kind:

  dense   — RMSNorm -> GQA attention -> RMSNorm -> MLP        (llama-style)
  moe     — RMSNorm -> GQA attention -> RMSNorm -> MoE FFN
  mamba1  — RMSNorm -> mamba1 mixer                           (falcon-mamba)
  mamba2  — RMSNorm -> mamba2/SSD mixer                       (zamba2)
  encdec  — whisper-style encoder block / decoder block with cross-attention

Stacks scan over layer-stacked parameter pytrees (leading axis = n_layers)
so HLO size is depth-independent; ``remat="block"`` wraps the block body in
``jax.checkpoint`` during training. Caches are stacked along the same axis
and scanned together with the params during decode.

MoE interleaving (llama4: every other layer) is expressed as a scan over
*groups* of ``moe_every`` layers — (moe_every-1) dense blocks + 1 MoE block
per group — so mixed stacks still scan. The zamba2 hybrid applies ONE
shared attention block (single param set, n_apps KV caches) every
``attn_every`` mamba2 layers via python-chunked sub-scans.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizers import QuantizedTensor, dequantize

from . import ssm
from .attention import attn_forward, attn_init, attn_logical
from .layers import dense_init, matmul_param, mlp_forward, mlp_init, mlp_logical, rmsnorm
from .moe import moe_forward, moe_init, moe_logical

# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def dense_block_init(key, cfg, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attn_init(k1, cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def dense_block_logical(cfg) -> dict:
    return {"ln1": ("p_unsharded",), "attn": attn_logical(cfg),
            "ln2": ("p_unsharded",), "mlp": mlp_logical(cfg.act)}


def dense_block_forward(p, x, cfg, ctx, rcfg, *, positions, cache=None,
                        cache_pos=None, causal=True, xa=None, use_kernel=False,
                        kv_spec=None, kv_kernel=False, kv_scales=None,
                        pages=None, page_size=None, paged_prefill=None):
    h, new_kv = attn_forward(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
                             ctx, rcfg, positions=positions, causal=causal,
                             cache=cache, cache_pos=cache_pos, xa=xa,
                             use_kernel=use_kernel, kv_spec=kv_spec,
                             kv_kernel=kv_kernel, kv_scales=kv_scales,
                             pages=pages, page_size=page_size,
                             paged_prefill=paged_prefill)
    x = x + h
    x = x + mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act,
                        ctx, use_kernel=use_kernel)
    return ctx.constrain(x, "batch", "seq", None), new_kv


def moe_block_init(key, cfg, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attn_init(k1, cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "moe": moe_init(k2, cfg, dtype),
    }


def moe_block_logical(cfg) -> dict:
    return {"ln1": ("p_unsharded",), "attn": attn_logical(cfg),
            "ln2": ("p_unsharded",), "moe": moe_logical(cfg)}


def moe_block_forward(p, x, cfg, ctx, rcfg, *, positions, cache=None,
                      cache_pos=None, use_kernel=False,
                      kv_spec=None, kv_kernel=False, kv_scales=None,
                      pages=None, page_size=None, paged_prefill=None):
    h, new_kv = attn_forward(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
                             ctx, rcfg, positions=positions, causal=True,
                             cache=cache, cache_pos=cache_pos,
                             use_kernel=use_kernel, kv_spec=kv_spec,
                             kv_kernel=kv_kernel, kv_scales=kv_scales,
                             pages=pages, page_size=page_size,
                             paged_prefill=paged_prefill)
    x = x + h
    x = x + moe_forward(p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, ctx,
                        use_kernel=use_kernel)
    return ctx.constrain(x, "batch", "seq", None), new_kv


def mamba_block_init(key, cfg, dtype=jnp.float32) -> dict:
    init = ssm.mamba1_init if cfg.family == "ssm" else ssm.mamba2_init
    return {"ln": jnp.ones((cfg.d_model,), dtype), "mix": init(key, cfg, dtype)}


def mamba_block_logical(cfg) -> dict:
    log = ssm.mamba1_logical() if cfg.family == "ssm" else ssm.mamba2_logical()
    return {"ln": ("p_unsharded",), "mix": log}


def mamba_block_forward(p, x, cfg, ctx, *, cache=None, use_kernel=False,
                        variant="mamba1"):
    fwd = ssm.mamba1_forward if variant == "mamba1" else ssm.mamba2_forward
    h, new_cache = fwd(p["mix"], rmsnorm(x, p["ln"], cfg.norm_eps), cfg, ctx,
                       cache=cache, use_kernel=use_kernel)
    return ctx.constrain(x + h, "batch", "seq", None), new_cache


def encdec_block_init(key, cfg, dtype=jnp.float32, cross: bool = False) -> dict:
    p = dense_block_init(key, cfg, dtype)
    if cross:
        k = jax.random.fold_in(key, 7)
        p["ln_x"] = jnp.ones((cfg.d_model,), dtype)
        p["xattn"] = attn_init(k, cfg, dtype)
    return p


def encdec_block_logical(cfg, cross: bool = False) -> dict:
    p = dense_block_logical(cfg)
    if cross:
        p["ln_x"] = ("p_unsharded",)
        p["xattn"] = attn_logical(cfg)
    return p


def decoder_xblock_forward(p, x, cfg, ctx, rcfg, *, positions, xa=None,
                           cache=None, cache_pos=None, use_kernel=False):
    """Whisper decoder block: self-attn (+cache) -> cross-attn -> MLP."""
    self_cache = None if cache is None else {"k": cache["k"], "v": cache["v"]}
    h, new_kv = attn_forward(p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
                             ctx, rcfg, positions=positions, causal=True,
                             cache=self_cache, cache_pos=cache_pos,
                             use_kernel=use_kernel)
    x = x + h
    if cache is not None and "xk" in cache:
        xcache = {"k_static": cache["xk"], "v_static": cache["xv"],
                  "len": cache["xlen"]}
        h, _ = attn_forward(p["xattn"], rmsnorm(x, p["ln_x"], cfg.norm_eps), cfg,
                            ctx, rcfg, positions=positions, cache=xcache,
                            cache_pos=cache_pos, use_kernel=use_kernel)
    else:
        h, xkv = attn_forward(p["xattn"], rmsnorm(x, p["ln_x"], cfg.norm_eps),
                              cfg, ctx, rcfg, positions=positions, xa=xa,
                              use_kernel=use_kernel)
    x = x + h
    x = x + mlp_forward(p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg.act,
                        ctx, use_kernel=use_kernel)
    return ctx.constrain(x, "batch", "seq", None), new_kv


# ---------------------------------------------------------------------------
# Stacked-layer utilities
# ---------------------------------------------------------------------------


def stack_init(block_init, key, n: int, *args, **kwargs):
    """vmap a per-layer init over n split keys -> leading layer axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(k, *args, **kwargs))(keys)


def stack_logical(block_logical) -> Any:
    """Prepend the 'layers' logical axis to every leaf of a block tree."""
    return jax.tree.map(lambda ax: ("layers", *ax), block_logical,
                        is_leaf=lambda x: isinstance(x, tuple))


def scan_blocks(body, x, stacked, rcfg, *, cache=None, length: int):
    """lax.scan over stacked layer params (+ optional stacked caches).

    body(x, layer_params, layer_cache) -> (x, new_layer_cache)
    Returns (x, new_stacked_cache). remat wraps the body when training.
    """
    fn = body
    if rcfg.remat == "block" and cache is None:
        fn = jax.checkpoint(body)

    def step(carry, xs):
        lp, lc = xs
        y, new_c = fn(carry, lp, lc)
        return y, new_c

    x, new_cache = jax.lax.scan(step, x, (stacked, cache), length=length)
    return x, new_cache


# ---------------------------------------------------------------------------
# Positional / embedding helpers
# ---------------------------------------------------------------------------


def sinusoid_table(max_len: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def embed_tokens(emb, tokens, ctx, dtype=jnp.bfloat16):
    """Embedding lookup. A quantized table is indexed before it is decoded:
    decoding all of it (yi-9b: 262M codes) on every step cost a gather over
    every code, which dominated a decode step on the TPU."""
    from .layers import param_value
    if isinstance(emb, QuantizedTensor):
        # scales are per output channel (1, d_model): they broadcast over rows
        rows = QuantizedTensor(jnp.take(emb.codes, tokens, axis=0), emb.scale,
                               emb.spec)
        x = dequantize(rows, dtype)
    else:
        x = jnp.take(param_value(emb, dtype), tokens, axis=0)
    return ctx.constrain(x, "batch", "seq", None)


def unembed(x, w, ctx, use_kernel=False):
    logits = matmul_param(x, w, use_kernel=use_kernel)
    return ctx.constrain(logits, "batch", "seq_attn", "vocab")
