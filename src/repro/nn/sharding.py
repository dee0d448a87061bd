"""Logical-axis sharding: one rules table, divisibility-aware fallbacks.

Every tensor in the model is annotated with *logical* axis names; a
``ShardingCtx`` maps them to mesh axes (GSPMD PartitionSpec) with automatic
fallback to replication when a dimension is not divisible by its mesh axis.

Key mappings (production mesh (pod, data, model)):

  batch      -> (pod, data)      DP across pods and the data axis
  p_embed    -> data             FSDP: params sharded over data, all-gathered
                                 per layer inside the scan
  heads/kv_heads/mlp/experts/vocab -> model   (tensor/expert parallel)
  head_dim_tp -> model           fallback TP for archs whose head counts
                                 don't divide the model axis (llama4's 40H):
                                 contracting-dim sharding; GSPMD turns the
                                 score/attend einsums into psum partials
  kv_seq     -> model            sequence-sharded KV cache for decode —
                                 GSPMD partitions the softmax reductions into
                                 the flash-decoding pattern (cheap all-reduce
                                 of per-chip max/sum stats instead of
                                 gathering a 500k-token cache)

Serving tensor parallelism (DESIGN.md §9) uses a second, 1-D mesh shape:
``("tp",)`` (``launch.mesh.make_tp_mesh``). Its rules table shards the
Megatron axes only — attention heads, MLP hidden, experts, and the decode
KV cache's head axis — and the serving engine runs the model *manually*
inside ``shard_map`` with a mesh-less ctx whose ``tp_axis`` is set:
``constrain`` no-ops and ``psum`` becomes the single cross-device
reduction each block issues after its row-sharded projection.

CPU smoke tests run with mesh=None: same code, no constraints.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingCtx", "make_ctx", "manual_tp_ctx", "shard_map_compat",
           "shard_policy_params", "logical_specs", "TP_AXIS"]

Logical = Union[str, None]

TP_AXIS = "tp"


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    mesh: Optional[Mesh]
    rules: "dict[str, Tuple[str, ...]]"
    # Set when model code runs *inside* a shard_map over a serving TP mesh:
    # every mesh axis is manual there, so GSPMD constraints are meaningless
    # (mesh is None) and collectives are explicit — ``psum`` is the one each
    # block calls after its row-sharded matmul.
    tp_axis: Optional[str] = None

    def axis_size(self, mesh_axis: str) -> int:
        if self.mesh is None or mesh_axis not in self.mesh.shape:
            return 1
        return self.mesh.shape[mesh_axis]

    def psum(self, x: jax.Array) -> jax.Array:
        """Sum partial results over the manual TP axis (no-op outside one).

        Correctness contract: callers invoke this exactly where a
        contraction dim was sharded by ``shard_policy_params`` (attention
        wo, MLP down-proj, the MoE expert combine) — the rules table and
        the divisibility *errors* (not fallbacks) in shard_policy_params
        guarantee those dims really are sharded whenever tp_axis is set.
        """
        if self.tp_axis is None:
            return x
        return jax.lax.psum(x, self.tp_axis)

    def spec(self, logical: Tuple[Logical, ...], shape: Tuple[int, ...]) -> P:
        """PartitionSpec for ``shape`` with divisibility + reuse fallbacks."""
        if self.mesh is None:
            return P()
        used: set = set()
        out = []
        for name, dim in zip(logical, shape):
            axes = self.rules.get(name) if name else None
            if not axes:
                out.append(None)
                continue
            picked = []
            prod = 1
            for ax in axes:
                if ax in used or ax not in self.mesh.shape:
                    continue
                prod *= self.mesh.shape[ax]
                picked.append(ax)
            if not picked or dim % prod != 0:
                out.append(None)
                continue
            used.update(picked)
            out.append(tuple(picked) if len(picked) > 1 else picked[0])
        return P(*out)

    def constrain(self, x: jax.Array, *logical: Logical) -> jax.Array:
        """with_sharding_constraint by logical names (no-op without a mesh)."""
        if self.mesh is None:
            return x
        assert len(logical) == x.ndim, (logical, x.shape)
        spec = self.spec(tuple(logical), x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def sharding(self, logical: Tuple[Logical, ...], shape: Tuple[int, ...]):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(tuple(logical), shape))


def make_ctx(mesh: Optional[Mesh], *, fsdp: bool = True,
             sequence_parallel: bool = False) -> ShardingCtx:
    """Build the rules table for whatever mesh we were given.

    sequence_parallel shards the *residual stream* (block inputs/outputs,
    norms, checkpointed activations) over the model axis along seq —
    Megatron-SP. Attention/MLP interiors stay head/mlp-sharded via the
    "seq_attn" alias; GSPMD inserts the all-gather/reduce-scatter pair at
    the block boundary. This is what lets 100B+ dense training fit HBM
    (the per-layer activation checkpoint shrinks by the model-axis size).
    """
    if mesh is None:
        return ShardingCtx(None, {})
    names = set(mesh.axis_names)
    if TP_AXIS in names:
        return ShardingCtx(mesh, _tp_rules())
    batch = tuple(a for a in ("pod", "data") if a in names)
    data = ("data",) if "data" in names else ()
    model = ("model",) if "model" in names else ()
    rules = {
        # activations
        "batch": batch,
        "seq": model if sequence_parallel else (),
        "seq_attn": (),             # seq inside attention/MLP (gathered)
        "embed": (),
        "heads": model,
        "kv_heads": model,
        "head_dim": (),
        "head_dim_tp": model,       # fallback TP (contracting-dim)
        "mlp": model,
        "experts": model,
        "vocab": model,
        "kv_seq": model,            # sequence-sharded decode cache
        "kv_heads_c": (),           # decode-cache head axis (TP mesh only)
        "expert_cap": (),
        # SSM: channel (d_inner) dims shard over model — in_proj columns,
        # out_proj rows (contraction -> psum), per-channel scan state.
        # §Perf iter A: these names previously had NO rule, which silently
        # replicated every mamba layer 16x over the model axis.
        "d_inner": model,
        "d_inner2": model,
        "d_inner_r": model,
        "heads_r": model,
        # params
        "p_embed": data if fsdp else (),
        "p_unsharded": (),
        "layers": (),
        "state": (),
        "conv": (),
    }
    return ShardingCtx(mesh, rules)


# ---------------------------------------------------------------------------
# Serving tensor parallelism over a 1-D ("tp",) mesh (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _tp_rules() -> dict:
    """Megatron-style serving TP: shard ONLY the axes whose row-sharded
    contraction has an explicit ``ctx.psum`` in the model code — attention
    heads (wq/wk/wv columns, wo rows via "mlp"), MLP hidden, experts — plus
    the decode KV cache's head axis. Everything else (embed/unembed, norms,
    router, SSM channel dims, activations) replicates: SSM blocks run
    replicated rather than splitting mamba's packed in_proj output, and the
    residual stream never shards, so slot logic stays device-count-agnostic.
    """
    tp = (TP_AXIS,)
    return {
        "heads": tp,
        "kv_heads": tp,
        "mlp": tp,                  # MLP hidden AND attention wo's row dim
        "experts": tp,
        "kv_heads_c": tp,           # decode-cache (B, G, S, Dh) head axis
    }


def manual_tp_ctx(axis: str = TP_AXIS) -> ShardingCtx:
    """Ctx for model code running inside a shard_map over the TP mesh:
    no mesh (constrain no-ops; every axis is manual), explicit psum."""
    return ShardingCtx(None, {}, tp_axis=axis)


def shard_map_compat(f, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` as both users call it (the TP serving engine and
    the posit8-compressed train step): ``check_vma=False`` because pallas
    calls inside carry no replication rule.

    ``manual_axes`` defaults to every mesh axis (the serving-TP case);
    pass a subset for partial-manual (train's pod-only grad transport).
    """
    manual = set(manual_axes if manual_axes is not None else mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=manual, check_vma=False)


def logical_specs(ctx: ShardingCtx, logical: Any, abstract: Any) -> Any:
    """PartitionSpec tree for a plain (non-quantized) pytree zipped against
    a logical-axis tree (leaves = tuples of axis names). Indivisible sharded
    dims raise (strict, like shard_policy_params): used for the TP decode
    cache, where a silently replicated head axis would desynchronize the
    per-device attention shards.
    """
    def one(ax, leaf):
        ax = tuple(ax)[:leaf.ndim]
        ax = ax + (None,) * (leaf.ndim - len(ax))
        return _strict_spec(ctx, ax, leaf.shape, "/".join(map(str, ax)))

    return jax.tree.map(one, logical, abstract,
                        is_leaf=lambda x: isinstance(x, tuple))


def shard_policy_params(params: Any, logical: Any, ctx: ShardingCtx) -> Any:
    """PartitionSpec tree for a (possibly policy-quantized) parameter tree.

    Plain leaves get the spec their logical axes name. ``QuantizedTensor``
    leaves get a QuantizedTensor-shaped spec node: codes take the logical
    spec; the scale leaf shards *with* its codes — same mesh axis on every
    dim where the scale varies (size == codes dim), replicated where it
    broadcasts (size 1). Sharding a quantized leaf is only valid when the
    per-channel scale layout is congruent with the sharded axis
    (``core.policy.validate_scale_sharding``) and the dim divides the mesh
    axis; both violations RAISE — a silent replication fallback would break
    the manual-psum contract (``ShardingCtx.psum``).
    """
    from repro.core.policy import validate_scale_sharding
    from repro.core.quantizers import QuantizedTensor

    is_qt = lambda x: isinstance(x, QuantizedTensor)
    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_qt)[0]
    treedef = jax.tree_util.tree_structure(params, is_leaf=is_qt)
    log_flat = jax.tree_util.tree_flatten(
        logical, is_leaf=lambda x: isinstance(x, tuple))[0]
    if len(flat) != len(log_flat):
        raise ValueError(
            f"params tree has {len(flat)} leaves but the logical tree names "
            f"{len(log_flat)}")
    out = []
    for (path, leaf), ax in zip(flat, log_flat):
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        shape = leaf.shape
        ax = tuple(ax) + (None,) * (len(shape) - len(ax))
        spec = _strict_spec(ctx, ax, shape, name)
        if not is_qt(leaf):
            out.append(spec)
            continue
        scale_spec = validate_scale_sharding(
            name, leaf.codes.shape, leaf.scale.shape, spec)
        out.append(QuantizedTensor(spec, scale_spec, leaf.spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def _strict_spec(ctx: ShardingCtx, logical, shape, name: str) -> P:
    """Like ``ShardingCtx.spec`` but indivisibility is an error, not a
    replication fallback: manual-mode psum correctness depends on the
    named dims actually being sharded."""
    if ctx.mesh is None:
        return P()
    out = []
    for axname, dim in zip(logical, shape):
        axes = ctx.rules.get(axname) if axname else None
        axes = tuple(a for a in (axes or ()) if a in ctx.mesh.shape)
        if not axes:
            out.append(None)
            continue
        prod = 1
        for a in axes:
            prod *= ctx.mesh.shape[a]
        if dim % prod != 0:
            raise ValueError(
                f"cannot tensor-parallel {name!r}: the {'x'.join(axes)} "
                f"mesh axis ({prod} devices) does not divide dim "
                f"{axname!r} of size {dim}; pick a tp that divides it")
        out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)
