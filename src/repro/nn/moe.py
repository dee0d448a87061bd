"""Mixture-of-Experts: top-k router + sort-based capacity dispatch.

Fixed-shape, MXU-friendly dispatch (MaxText/GShard "dropping" style, but via
sort instead of dense one-hot einsums so dispatch cost is O(T k log T), not
O(T·E·C·d)):

  1. router logits (f32, never quantized — see DESIGN.md §5) -> top-k ids
  2. stable-sort the T*k (expert, token) assignments by expert
  3. position-in-expert via searchsorted; tokens beyond capacity C drop
  4. scatter to (E, C, d) -> per-expert batched matmuls (MXU) -> gather back

Expert weights are sharded over the model axis (EP); the (E, C, d) dispatch
resharding is where GSPMD emits the all-to-all the paper's communication
column talks about. Expert FFN weights are the paper's best posit case:
n_experts copies of cold parameters (quantizable via QuantSpec like any
other matmul weight).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import (activation, dense_init, is_gated, matmul_param,
                     mlp_init, mlp_logical, param_value)


def moe_init(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    gated = is_gated(cfg.act)
    p = {"router": dense_init(ks[0], d, E, dtype=jnp.float32)}
    def ew(k, i, o):  # stacked expert weights (E, in, out)
        return dense_init(k, E, (i, o), scale=i ** -0.5, dtype=dtype)
    if gated:
        p.update(wg=ew(ks[1], d, ff), wu=ew(ks[2], d, ff), wo=ew(ks[3], ff, d))
    else:
        p.update(wi=ew(ks[1], d, ff), wo=ew(ks[3], ff, d))
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, ff * cfg.n_shared_experts, cfg.act, dtype)
    return p


def moe_logical(cfg) -> dict:
    gated = is_gated(cfg.act)
    p = {"router": ("p_unsharded", "p_unsharded")}
    if gated:
        p.update(wg=("experts", "p_embed", None), wu=("experts", "p_embed", None),
                 wo=("experts", None, "p_embed"))
    else:
        p.update(wi=("experts", "p_embed", None), wo=("experts", None, "p_embed"))
    if cfg.n_shared_experts:
        p["shared"] = mlp_logical(cfg.act)
    return p


def moe_forward(p: dict, x: jax.Array, cfg, ctx, use_kernel: bool = False) -> jax.Array:
    """x: (B, S, d) -> (B, S, d).

    Decode (S == 1) uses drop-free capacity C = T*k — a handful of tokens;
    train/prefill uses the GShard capacity factor (dropping is part of the
    algorithm there, and keeps shapes static for the MXU).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)
    # 1. route (f32 for numerical routing stability)
    logits = jnp.dot(xt.astype(jnp.float32), param_value(p["router"], jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # 2. sort assignments by expert
    flat_expert = topk_idx.reshape(-1)                      # (T*k,)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    # 3. position within expert, capacity mask
    C = T * k if S == 1 else int(max(1, round(T * k * cfg.capacity_factor / E)))
    starts = jnp.searchsorted(sorted_expert, jnp.arange(E))
    pos = jnp.arange(T * k) - starts[sorted_expert]
    keep = pos < C
    slot = jnp.where(keep, sorted_expert * C + pos, E * C)  # drop row at E*C
    token_of = order // k
    # 4. scatter -> (E, C, d)
    disp = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(
        xt[token_of] * keep[:, None].astype(x.dtype))
    disp = disp[:-1].reshape(E, C, d)
    disp = ctx.constrain(disp, "experts", "expert_cap", None)
    # 5. expert FFN (batched over E; EP-sharded). Inside a manual-TP
    # shard_map the weight leaves carry only E/tp local experts: routing
    # and dispatch ran replicated over the GLOBAL expert ids above, so each
    # device slices its expert rows out of the dispatch, computes its local
    # FFNs, and scatters the results back into the global (E*C, d) layout —
    # rows of non-local experts stay zero and the combine's psum below sums
    # the disjoint per-device partials into the full mixture.
    E_loc = p["wo"].shape[0]
    if E_loc != E:
        e0 = jax.lax.axis_index(ctx.tp_axis) * E_loc
        disp_e = jax.lax.dynamic_slice_in_dim(disp, e0, E_loc, axis=0)
    else:
        disp_e = disp
    fn = activation(cfg.act)
    if is_gated(cfg.act):
        g = jnp.einsum("ecd,edf->ecf", disp_e, param_value(p["wg"], x.dtype))
        u = jnp.einsum("ecd,edf->ecf", disp_e, param_value(p["wu"], x.dtype))
        h = fn(g) * u
    else:
        h = fn(jnp.einsum("ecd,edf->ecf", disp_e, param_value(p["wi"], x.dtype)))
    h = ctx.constrain(h, "experts", "expert_cap", None)
    out_e = jnp.einsum("ecf,efd->ecd", h, param_value(p["wo"], x.dtype))
    out_e = ctx.constrain(out_e, "experts", "expert_cap", None)
    if E_loc != E:
        out_e = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((E, C, d), out_e.dtype), out_e, e0, axis=0)
    # 6. gather back + weighted combine
    out_flat = out_e.reshape(E * C, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.clip(slot, 0, E * C - 1)], 0.0)
    gates_sorted = gate_vals.reshape(-1)[order]
    contrib = gathered * gates_sorted[:, None].astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[token_of].add(contrib)
    if E_loc != E:
        y = ctx.psum(y)     # the MoE block's one expert-combine collective
    if cfg.n_shared_experts:
        from .layers import mlp_forward
        # mlp_forward psums its own (tp-sharded) down-proj, so the shared
        # contribution adds in AFTER the expert psum — full on every device.
        y = y + mlp_forward(p["shared"], xt[None], cfg.act, ctx,
                            use_kernel=use_kernel)[0]
    return y.reshape(B, S, d)


def router_aux_loss(p, x, cfg) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style f*P)."""
    T = x.shape[0] * x.shape[1]
    logits = jnp.dot(x.reshape(T, -1).astype(jnp.float32), param_value(p["router"], jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, topk_idx = jax.lax.top_k(probs, cfg.top_k)
    frac = jnp.mean(jax.nn.one_hot(topk_idx, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac * probs.mean(0))
