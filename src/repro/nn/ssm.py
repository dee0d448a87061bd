"""State-space sequence mixers: Mamba1 selective scan and Mamba2 SSD.

Both are implemented chunk-parallel so the (B, S, d_inner, state) tensor is
never materialized over the full sequence:

* Mamba1 (falcon-mamba): per-channel diagonal A. Within a chunk of length c
  the recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t is solved in closed
  form with cumulative sums (log-space prefix products), and chunk-to-chunk
  state is carried by a small jax.lax.scan over S/c steps. This is the
  TPU-native port of the CUDA selective-scan kernel: the FPGA/GPU trick
  (fused recurrent kernel) becomes "batched matmul-sized chunks + tiny carry
  scan", which keeps the MXU/VPU busy instead of emulating a serial loop.

* Mamba2 (zamba2): scalar-per-head A (SSD). The chunked SSD algorithm of the
  Mamba2 paper maps 1:1 onto MXU matmuls: intra-chunk (C B^T ⊙ L) X plus
  inter-chunk state passing. chunk = cfg.ssm_chunk.

Decode is the exact single-step recurrence against a carried (B, ...) state
(the SSM analogue of a KV cache; size is sequence-independent, which is why
the long_500k cell is assigned to these families).

Parameter quantization (the paper's technique) applies to the in/out/x
projections; A_log, dt_bias, D and norms stay fp32 — the recurrence is
error-accumulating (documented inapplicability, DESIGN.md §5).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Param, dense_init, matmul_param, param_value, rmsnorm

# ---------------------------------------------------------------------------
# Mamba1 (falcon-mamba-7b)
# ---------------------------------------------------------------------------


def mamba1_init(key, cfg, dtype=jnp.float32) -> dict:
    d, di, ds, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    # A initialized to -[1..ds] per channel (S4D-real), stored as log.
    # (host constants: XLA would constant-fold a jnp.log inside a jit with
    # other rounding than it computes it eagerly)
    a = np.tile(np.arange(1, ds + 1, dtype=np.float64)[None, :], (di, 1))
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype=dtype),
        "conv_w": dense_init(ks[1], cfg.conv_width, di, scale=0.1, dtype=dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": dense_init(ks[2], di, dr + 2 * ds, dtype=dtype),
        "dt_proj": dense_init(ks[3], dr, di, scale=dr**-0.5, dtype=dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(
            jax.random.uniform(ks[4], (di,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1))
        ))).astype(jnp.float32),
        "A_log": jnp.asarray(np.log(a), jnp.float32),   # fp32 always
        "D": jnp.ones((di,), jnp.float32),         # fp32 always
        "out_proj": dense_init(ks[5], di, d, dtype=dtype),
    }


def mamba1_logical() -> dict:
    return {
        "in_proj": ("p_embed", "d_inner"),
        "conv_w": ("conv", "d_inner"),
        "conv_b": ("d_inner",),
        "x_proj": ("d_inner_r", "p_unsharded"),
        "dt_proj": ("p_unsharded", "d_inner"),
        "dt_bias": ("d_inner",),
        "A_log": ("d_inner", "state"),
        "D": ("d_inner",),
        "out_proj": ("d_inner_r", "p_embed"),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over seq. x: (B, S, d); w: (K, d).

    state: (B, K-1, d) trailing inputs from the previous segment (decode /
    chunked prefill). Returns (y, new_state).
    """
    K = w.shape[0]
    B, S, d = x.shape
    if state is None:
        state = jnp.zeros((B, K - 1, d), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = jnp.zeros((B, S, d), jnp.float32)
    for i in range(K):  # K is 4: unrolled taps, no conv primitive needed
        y = y + xp[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    # keep the cache dtype stable across steps: a decode cache initialized
    # f32 must not silently become bf16 after the first step (the engine
    # scans decode_step, and a lax.scan carry rejects the dtype flip)
    new_state = xp[:, S:].astype(state.dtype)
    return (y + b.astype(jnp.float32)).astype(x.dtype), new_state


def _chunk_scan_diag(dA: jax.Array, dBx: jax.Array, h0: jax.Array):
    """Solve h_t = dA_t * h_{t-1} + dBx_t within a chunk, diagonal dA.

    dA, dBx: (B, c, ...) with matching trailing dims; h0: (B, ...).
    Returns (h_all (B, c, ...), h_last). Associative scan over the linear
    recurrence: composing (A1,b1) then (A2,b2) gives (A2*A1, A2*b1 + b2).
    All products stay in (0, 1] (dA = exp(dt*A), A < 0), so this is
    overflow-free where the naive 1/prefix-product rescale was not.
    """
    def comb(left, right):
        a1, b1 = left
        a2, b2 = right
        return a2 * a1, a2 * b1 + b2

    A, Bv = jax.lax.associative_scan(comb, (dA, dBx), axis=1)
    h = A * h0[:, None] + Bv
    return h, h[:, -1]


def mamba1_mix(p: dict, xz: jax.Array, cfg, *, conv_state=None, ssm_state=None,
               chunk: Optional[int] = None):
    """Core mamba1 mixer after in_proj. xz: (B, S, 2*di).

    Returns (y (B, S, di-projected d), new_conv_state, new_ssm_state).
    """
    di, ds, dr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    x, z = jnp.split(xz, 2, axis=-1)
    x, new_conv = _causal_conv(x, param_value(p["conv_w"], jnp.float32),
                               param_value(p["conv_b"], jnp.float32), conv_state)
    x = jax.nn.silu(x)
    # input-dependent dt, B, C
    dbc = matmul_param(x, p["x_proj"])
    dt, Bm, Cm = jnp.split(dbc, [dr, dr + ds], axis=-1)
    dt = matmul_param(dt, p["dt_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # (B,S,di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                  # (di,ds)
    B_, S_, _ = x.shape
    c = chunk or min(cfg.ssm_chunk, S_)
    while S_ % c:
        c -= 1
    xf = x.astype(jnp.float32)
    dA = jnp.exp(dt[..., None] * A)                               # (B,S,di,ds)
    dBx = (dt * xf)[..., None] * Bm.astype(jnp.float32)[..., None, :]
    if ssm_state is None:
        ssm_state = jnp.zeros((B_, di, ds), jnp.float32)

    def step(h, blk):
        dA_c, dBx_c, C_c = blk
        h_all, h_last = _chunk_scan_diag(dA_c, dBx_c, h)
        y_c = jnp.einsum("bcds,bcs->bcd", h_all, C_c)
        return h_last, y_c

    n = S_ // c
    blocks = (
        dA.reshape(B_, n, c, di, ds).swapaxes(0, 1),
        dBx.reshape(B_, n, c, di, ds).swapaxes(0, 1),
        Cm.astype(jnp.float32).reshape(B_, n, c, ds).swapaxes(0, 1),
    )
    h_last, ys = jax.lax.scan(step, ssm_state, blocks)
    y = ys.swapaxes(0, 1).reshape(B_, S_, di)
    y = y + xf * p["D"].astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(xz.dtype), new_conv, h_last


def mamba1_forward(p: dict, x: jax.Array, cfg, ctx, *, cache: Optional[dict] = None,
                   use_kernel: bool = False):
    """Full mamba1 block. x: (B, S, d). cache: {"conv": ..., "ssm": ...}."""
    xz = matmul_param(x, p["in_proj"], use_kernel=use_kernel)
    xz = ctx.constrain(xz, "batch", "seq_attn", "d_inner2")
    conv_s = cache["conv"] if cache else None
    ssm_s = cache["ssm"] if cache else None
    y, new_conv, new_ssm = mamba1_mix(p, xz, cfg, conv_state=conv_s, ssm_state=ssm_s)
    out = matmul_param(y, p["out_proj"], use_kernel=use_kernel)
    new_cache = {"conv": new_conv, "ssm": new_ssm} if cache is not None else None
    return out, new_cache


def mamba1_init_cache(cfg, batch: int, dtype=jnp.float32) -> dict:
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# Mamba2 / SSD (zamba2)
# ---------------------------------------------------------------------------


def mamba2_init(key, cfg, dtype=jnp.float32) -> dict:
    d, di, ds = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh = di // cfg.ssm_head_dim
    ks = jax.random.split(key, 4)
    # in_proj packs [z (di), x (di), B (ds), C (ds), dt (nh)]
    return {
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * ds + nh, dtype=dtype),
        "conv_w": dense_init(ks[1], cfg.conv_width, di + 2 * ds, scale=0.1,
                             dtype=dtype),
        "conv_b": jnp.zeros((di + 2 * ds,), dtype),
        "A_log": jnp.asarray(np.log(np.linspace(1.0, 16.0, nh)), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[2], di, d, dtype=dtype),
    }


def mamba2_logical() -> dict:
    return {
        "in_proj": ("p_embed", "d_inner2"),
        "conv_w": ("conv", "d_inner2"),
        "conv_b": ("d_inner2",),
        "A_log": ("heads_r",),
        "dt_bias": ("heads_r",),
        "D": ("heads_r",),
        "norm_w": ("d_inner",),
        "out_proj": ("d_inner_r", "p_embed"),
    }


def _ssd_chunk(x, dt, A, Bm, Cm, h0):
    """One SSD chunk. x: (B,c,nh,dh); dt: (B,c,nh); A: (nh,) negative;
    Bm/Cm: (B,c,ds); h0: (B,nh,dh,ds). Returns (y, h_last).

    Mamba2 alg: with a_t = exp(dt_t A) per head,
      intra: y_t  = C_t · sum_{i<=t} (prod_{i<j<=t} a_j) dt_i B_i x_i
      inter: y_t += C_t · (prod_{i<=t} a_i) h0
    realized as matmuls with the L (decay) mask — all MXU work.
    """
    Bsz, c, nh, dh = x.shape
    la = dt * A  # (B,c,nh) log decay, <= 0
    cum = jnp.cumsum(la, axis=1)                       # log prod_{i<=t}
    # L[t, i] = exp(cum_t - cum_i) for i <= t else 0  (decay from i+1..t).
    # Mask BEFORE exp: the i > t entries are positive and overflow to inf,
    # and inf * 0 in the select backward poisons the gradient.
    Lm = cum[:, :, None, :] - cum[:, None, :, :]        # (B,t,i,nh)
    tri = jnp.tril(jnp.ones((c, c), bool))
    Lm = jnp.exp(jnp.where(tri[None, :, :, None], Lm, -1e30))
    CB = jnp.einsum("bts,bis->bti", Cm, Bm)             # (B,t,i)
    W = CB[..., None] * Lm                              # (B,t,i,nh)
    dx = dt[..., None] * x                              # (B,c,nh,dh)
    y = jnp.einsum("btih,bihd->bthd", W, dx)            # intra-chunk
    # inter-chunk: contribution of the incoming state h0, decayed to step t
    decay0 = jnp.exp(cum)                               # (B,c,nh)
    y = y + jnp.einsum("bts,bhds,bth->bthd", Cm, h0, decay0)
    # state update: h_last = exp(cum_last) h0 + sum_i exp(cum_last - cum_i) dt_i B_i x_i
    w_last = jnp.exp(cum[:, -1:, :] - cum)              # (B,c,nh)
    h_last = (jnp.exp(cum[:, -1])[:, :, None, None] * h0
              + jnp.einsum("bih,bihd,bis->bhds", w_last, dx, Bm))
    return y, h_last


def mamba2_mix(p: dict, zxbcdt: jax.Array, cfg, *, conv_state=None, ssm_state=None,
               chunk: Optional[int] = None):
    """Core mamba2 mixer after in_proj. zxbcdt: (B, S, 2di+2ds+nh)."""
    di, ds, dh = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
    nh = di // dh
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * ds], axis=-1)
    xBC, new_conv = _causal_conv(xBC, param_value(p["conv_w"], jnp.float32),
                                 param_value(p["conv_b"], jnp.float32), conv_state)
    xBC = jax.nn.silu(xBC)
    x, Bm, Cm = jnp.split(xBC, [di, di + ds], axis=-1)
    Bsz, S, _ = x.shape
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))        # (nh,)
    xh = x.astype(jnp.float32).reshape(Bsz, S, nh, dh)
    c = chunk or min(cfg.ssm_chunk, S)
    while S % c:
        c -= 1
    n = S // c
    if ssm_state is None:
        ssm_state = jnp.zeros((Bsz, nh, dh, ds), jnp.float32)

    def step(h, blk):
        x_c, dt_c, B_c, C_c = blk
        y_c, h_last = _ssd_chunk(x_c, dt_c, A, B_c, C_c, h)
        return h_last, y_c

    blocks = (
        xh.reshape(Bsz, n, c, nh, dh).swapaxes(0, 1),
        dt.reshape(Bsz, n, c, nh).swapaxes(0, 1),
        Bm.astype(jnp.float32).reshape(Bsz, n, c, ds).swapaxes(0, 1),
        Cm.astype(jnp.float32).reshape(Bsz, n, c, ds).swapaxes(0, 1),
    )
    h_last, ys = jax.lax.scan(step, ssm_state, blocks)
    y = ys.swapaxes(0, 1).reshape(Bsz, S, nh, dh)
    y = y + xh * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(Bsz, S, di)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rmsnorm(y.astype(zxbcdt.dtype), p["norm_w"], cfg.norm_eps)
    return y, new_conv, h_last


def mamba2_forward(p: dict, x: jax.Array, cfg, ctx, *, cache: Optional[dict] = None,
                   use_kernel: bool = False):
    zxbcdt = matmul_param(x, p["in_proj"], use_kernel=use_kernel)
    zxbcdt = ctx.constrain(zxbcdt, "batch", "seq_attn", "d_inner2")
    conv_s = cache["conv"] if cache else None
    ssm_s = cache["ssm"] if cache else None
    y, new_conv, new_ssm = mamba2_mix(p, zxbcdt, cfg, conv_state=conv_s, ssm_state=ssm_s)
    out = matmul_param(y, p["out_proj"], use_kernel=use_kernel)
    new_cache = {"conv": new_conv, "ssm": new_ssm} if cache is not None else None
    return out, new_cache


def mamba2_init_cache(cfg, batch: int, dtype=jnp.float32) -> dict:
    di, ds, dh = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
    nh = di // dh
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, di + 2 * ds), dtype),
        "ssm": jnp.zeros((batch, nh, dh, ds), jnp.float32),
    }


# Sequential references (the correctness oracles for tests) -----------------


def mamba1_mix_ref(p: dict, xz: jax.Array, cfg):
    """Naive per-timestep recurrence, float64-free but step-exact."""
    return mamba1_mix(p, xz, cfg, chunk=1)


def mamba2_mix_ref(p: dict, zxbcdt: jax.Array, cfg):
    return mamba2_mix(p, zxbcdt, cfg, chunk=1)
