"""Pallas TPU kernel: paged quantized-KV-cache flash-decode attention.

``kv_flash_decode`` streams a *contiguous* per-slot code cache; this kernel
is the same online-softmax decode indirected through a **block table**: the
cache is one flat pool of fixed-size token pages (byte-wide fxp/pofx codes,
DESIGN.md §10) and each slot names its pages by physical id. The block
table rides in as a scalar-prefetch operand (``PrefetchScalarGridSpec``),
so the grid's S axis walks *logical* pages while the BlockSpec index_map
DMAs the *physical* page — the indirection costs an SMEM lookup, not a
gather: only the slot's own pages ever leave HBM, and they dequantize on
the VPU in VMEM exactly as in the dense kernel.

Why this preserves the paper's bandwidth win: pages hold codes, so a page
of ``ps`` tokens moves ``ps * Dh`` bytes instead of ``2 * ps * Dh`` — and
because pages are position-masked (``idx >= pos`` lanes go to -inf),
garbage-page entries (unallocated tail of the table) and the junk beyond a
shared partial page's valid prefix are computed over but never survive the
softmax, so no per-slot trimming DMA is needed.

Oracle: ``ref.kv_flash_paged_decode_ref`` (gather pages -> dense oracle);
the XLA fallback in ``nn.attention`` computes the same gather out-of-place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantizers import QuantSpec
from . import vmem_scratch
from .kv_flash_decode import _dequant_tile

__all__ = ["kv_flash_paged_decode"]

NEG_INF = -1e30


def _kernel(tbl_ref, pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, spec: QuantSpec, ps: int, ns: int,
            scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (R, Dh)
    k = _dequant_tile(kc_ref[0, 0], spec, ks_ref[0])        # (ps, Dh)
    sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (R,ps)
    # logical token index of each lane in this page; everything at or past
    # the slot's valid length masks out — including the whole page when the
    # table entry is the garbage page (its logical index is past pos too)
    idx = s * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    sc = jnp.where(idx < pos_ref[b], sc, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]                 # (R, 1)
    m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)                                 # (R, ps)
    corr = jnp.exp(m_prev - m_new)
    v = _dequant_tile(vc_ref[0, 0], spec, vs_ref[0])        # (ps, Dh)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "interpret",
                                             "out_dtype"))
def kv_flash_paged_decode(q: jax.Array, k_pool: jax.Array, k_scale: jax.Array,
                          v_pool: jax.Array, v_scale: jax.Array,
                          tables: jax.Array, pos: jax.Array,
                          spec: QuantSpec, *, interpret: bool | None = None,
                          out_dtype=jnp.float32) -> jax.Array:
    """One-token attention against a paged quantized code pool.

    q:        (B, G, R, Dh) float queries (R = q heads per kv group)
    k_pool:   (n_pages, G, ps, Dh) int8/uint8 page pool (``kv_code_dtype``)
    k_scale:  (G, 1, Dh) f32 static per-head-dim-channel normalizer —
              global per layer, NOT per slot: pages are shareable across
              requests only because every page quantizes under one grid
    v_pool / v_scale: same layouts for V
    tables:   (B, max_pages) int32 physical page ids per slot (garbage-page
              padded past the allocated prefix)
    pos:      scalar or (B,) valid-prefix lengths (mask: idx < pos)

    Returns (B, G, R, Dh) in ``out_dtype``. Grid is (B, G, max_pages) with
    the page axis innermost; the block table is a scalar-prefetch operand
    so each page's physical id resolves before its DMA is issued. The
    block length is one page — pick page_size >= the backend's lane tile
    for production TPU runs (any size works in interpret mode).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, G, R, Dh = q.shape
    n_pages, Gp, ps, Dhp = k_pool.shape
    if v_pool.shape != k_pool.shape:
        raise ValueError(
            f"k/v pool shape mismatch: {k_pool.shape} vs {v_pool.shape}")
    if (Gp, Dhp) != (G, Dh):
        raise ValueError(
            f"pool (G, Dh) {Gp, Dhp} does not match queries {(G, Dh)}")
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc.shape != (G, 1, Dh):
            # must raise: the (1, Dh) BlockSpec would silently read row 0
            # of a mis-shaped scale while the XLA fallback broadcasts it
            raise ValueError(
                f"paged kv {name} must be global per-head-dim-channel "
                f"({G}, 1, {Dh}); got {sc.shape}")
    if tables.ndim != 2 or tables.shape[0] != B:
        raise ValueError(
            f"tables must be (B={B}, max_pages); got {tables.shape}")
    ns = tables.shape[1]
    pos2 = jnp.broadcast_to(jnp.reshape(pos, (-1,)), (B,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tables, pos
        grid=(B, G, ns),
        in_specs=[
            pl.BlockSpec((1, 1, R, Dh), lambda b, g, s, tbl, pos: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dh),
                         lambda b, g, s, tbl, pos: (tbl[b, s], g, 0, 0)),
            pl.BlockSpec((1, 1, Dh), lambda b, g, s, tbl, pos: (g, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dh),
                         lambda b, g, s, tbl, pos: (tbl[b, s], g, 0, 0)),
            pl.BlockSpec((1, 1, Dh), lambda b, g, s, tbl, pos: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, R, Dh),
                               lambda b, g, s, tbl, pos: (b, g, 0, 0)),
        scratch_shapes=[vmem_scratch((R, 1)), vmem_scratch((R, 1)),
                        vmem_scratch((R, Dh))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, ps=ps, ns=ns,
                          scale=Dh ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, R, Dh), out_dtype),
        interpret=interpret,
        name="kv_flash_paged_decode",
    )(tables.astype(jnp.int32), pos2, q.astype(jnp.float32), k_pool,
      k_scale.astype(jnp.float32), v_pool, v_scale.astype(jnp.float32))
    return out
