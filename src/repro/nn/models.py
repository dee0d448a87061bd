"""Model facade: one ``LM`` object per (ModelConfig, RunConfig, mesh).

Provides, for every assigned family (dense / moe / encdec / ssm / hybrid):

  init(key)            parameters (layer-stacked pytree, fp32 or bf16)
  init_quantized()     init + apply_policy, one layer at a time (serving)
  logical()            logical-axis tree (same treedef) for sharding
  abstract_params()    ShapeDtypeStruct tree via eval_shape (dry-run: no alloc)
  forward()            full-sequence logits (train / prefill)
  loss()               vocab-parallel cross-entropy (+ MoE aux loss)
  train_step()         grad accumulation + clip + AdamW (see repro.optim)
  init_cache()         decode state (KV / SSM), sequence- or batch-sharded
  prefill()/decode_step()  serving path; cache donated by the launcher

The paper's technique enters through ``apply_policy`` (uniform back-compat
shim: ``quantize_params``): eligible matmul weights become
``QuantizedTensor`` (normalized-posit codes + normalizer scale) in the
format the QuantPolicy's path rules assign them; every layer dispatches
through ``matmul_param`` which routes quantized weights to the PoFx
datapath, so mixed per-layer formats coexist in one forward pass. Norms /
SSM recurrence params / router weights are excluded (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.policy import QuantPolicy
from repro.core.quantizers import (QuantSpec, QuantizedTensor, kv_code_dtype,
                                   quantize, validate_kv_spec)
from .layers import dense_init, matmul_param, param_value, rmsnorm
from .sharding import ShardingCtx, make_ctx
from . import transformer as T
from . import ssm as S

__all__ = ["LM", "build_model", "apply_policy", "quantize_params",
           "input_specs", "ce_loss", "kv_decode_bytes_per_token"]


def _dt(name: str):
    return {"f32": jnp.float32, "fp32": jnp.float32, "bf16": jnp.bfloat16}[name]


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def ce_loss(logits: jax.Array, labels: jax.Array, *, z_weight: float = 0.0):
    """Vocab-parallel cross-entropy. logits (B,S,V) may be vocab-sharded;
    every reduction is over the V axis so GSPMD lowers to per-shard partials
    + a small all-reduce (no logits all-gather)."""
    lg = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    shifted = lg - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, lg.shape[-1], dtype=lg.dtype)
    ll = jnp.sum(lg * onehot, axis=-1)
    nll = jnp.mean(lse - ll)
    if z_weight:
        nll = nll + z_weight * jnp.mean(jnp.square(lse))
    return nll


# ---------------------------------------------------------------------------
# LM facade
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LM:
    cfg: ModelConfig
    rcfg: RunConfig
    ctx: ShardingCtx
    use_kernel: bool = False
    # Decode KV-cache format (DESIGN.md §8): a byte-wide fxp/pofx QuantSpec
    # makes init_cache allocate code+scale leaves and routes decode through
    # the quantized datapath; None keeps the bf16/f32 cache. kv_kernel
    # selects the fused Pallas flash-decode kernel (None: follow
    # use_kernel) vs the XLA quantize-on-write/dequantize-on-read fallback.
    kv_spec: Optional[QuantSpec] = None
    kv_kernel: Optional[bool] = None

    def __post_init__(self):
        self.kv_spec = validate_kv_spec(self.kv_spec)

    @property
    def kv_use_kernel(self) -> bool:
        return self.use_kernel if self.kv_kernel is None else self.kv_kernel

    # -- construction helpers ------------------------------------------------

    @property
    def act_dtype(self):
        return _dt(getattr(self.rcfg, "activation_dtype", "bf16"))

    @property
    def param_dtype(self):
        return _dt(self.rcfg.weight_dtype)

    @property
    def n_groups(self) -> int:
        cfg = self.cfg
        if cfg.family == "moe":
            return cfg.n_layers // cfg.moe_every
        return cfg.n_layers

    def _hybrid_chunks(self):
        """zamba2: layer-count chunks between shared-block applications."""
        cfg = self.cfg
        k, L = cfg.attn_every, cfg.n_layers
        sizes = []
        done = 0
        while done < L:
            sizes.append(min(k, L - done))
            done += sizes[-1]
        return sizes

    # -- init / logical -------------------------------------------------------

    def init(self, key) -> Dict[str, Any]:
        """Random parameters in ``weight_dtype``, layer-stacked."""
        return self._init(key, lambda path, *args: T.stack_init(*args))

    def init_quantized(self, key, policy) -> Dict[str, Any]:
        """``apply_policy(self.init(key), policy)`` bit for bit, as one jitted
        program that never holds the float model.

        Each layer-stacked leaf is made one layer at a time (``lax.map`` over
        the per-layer keys ``stack_init`` would vmap over) and quantized
        before the next layer is made, so only codes, scales and
        never-quantized leaves are ever full size. On a serving TP mesh the
        parameters come out sharded as the engine places them, so no device
        ever holds the whole model.
        """
        if isinstance(policy, str):
            policy = QuantPolicy.from_string(policy)

        def stack(path, block_init, k, n, *args):
            return jax.lax.map(
                lambda kk: _apply_policy(block_init(kk, *args), policy,
                                         prefix=path, mapped=1),
                jax.random.split(k, n))

        def init(key):
            params = self._init(key, stack)
            stacked = ("blocks", "enc_blocks")
            params.update(_apply_policy(
                {k: v for k, v in params.items() if k not in stacked}, policy))
            return params

        shardings = None
        if self.tp_size > 1:
            specs = self.param_tp_specs(jax.eval_shape(init, key))
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.ctx.mesh, s), specs)
        return jax.jit(init, out_shardings=shardings)(key)

    def _init(self, key, stack) -> Dict[str, Any]:
        """The parameter tree; ``stack(path, block_init, key, n, *args)``
        makes the ``n``-layer stack at ``path``."""
        cfg, dt = self.cfg, self.param_dtype
        ks = jax.random.split(key, 8)
        V, d = cfg.padded_vocab, cfg.d_model
        params: Dict[str, Any] = {
            "embed": dense_init(ks[0], V, d, scale=1.0, dtype=dt),
            "ln_f": jnp.ones((d,), dt),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(ks[1], d, V, dtype=dt)
        fam = cfg.family
        if fam == "dense":
            params["blocks"] = stack(("blocks",), T.dense_block_init, ks[2],
                                     cfg.n_layers, cfg, dt)
        elif fam == "moe":
            ng = self.n_groups
            params["blocks"] = {"moe": stack(("blocks", "moe"),
                                             T.moe_block_init, ks[2], ng,
                                             cfg, dt)}
            if cfg.moe_every > 1:
                def group_dense(k, cfg=cfg, dt=dt):
                    kk = jax.random.split(k, cfg.moe_every - 1)
                    return jax.vmap(lambda q: T.dense_block_init(q, cfg, dt))(kk)
                params["blocks"]["dense"] = stack(("blocks", "dense"),
                                                  group_dense, ks[3], ng)
        elif fam == "encdec":
            params["enc_blocks"] = stack(("enc_blocks",), T.encdec_block_init,
                                         ks[2], cfg.n_enc_layers, cfg, dt)
            params["enc_ln"] = jnp.ones((d,), dt)
            params["blocks"] = stack(
                ("blocks",), functools.partial(T.encdec_block_init, cross=True),
                ks[3], cfg.n_layers, cfg, dt)
        elif fam == "ssm":
            params["blocks"] = stack(("blocks",), T.mamba_block_init, ks[2],
                                     cfg.n_layers, cfg, dt)
        elif fam == "hybrid":
            params["blocks"] = stack(("blocks",), T.mamba_block_init, ks[2],
                                     cfg.n_layers, cfg, dt)
            params["shared"] = T.dense_block_init(ks[3], cfg, dt)
        else:
            raise ValueError(f"unknown family {fam!r}")
        return params

    def logical(self) -> Dict[str, Any]:
        cfg = self.cfg
        # Under posit8 gradient compression the step runs inside a
        # shard_map whose "pod" axis is manual; XLA's PartitionGather
        # CHECK-fails on a gather from a vocab-sharded table in that mode,
        # so the embed table keeps its vocab dim replicated there (the
        # d_model dim still FSDP-shards; unembed stays vocab-parallel —
        # matmuls partition fine).
        compressed = str(self.rcfg.grad_compression).startswith("posit8")
        out: Dict[str, Any] = {
            "embed": (None if compressed else "vocab", "p_embed"),
            "ln_f": ("p_unsharded",),
        }
        if not cfg.tie_embeddings:
            out["unembed"] = ("p_embed", "vocab")
        fam = cfg.family
        if fam == "dense":
            out["blocks"] = T.stack_logical(T.dense_block_logical(cfg))
        elif fam == "moe":
            out["blocks"] = {"moe": T.stack_logical(T.moe_block_logical(cfg))}
            if cfg.moe_every > 1:
                out["blocks"]["dense"] = T.stack_logical(
                    T.stack_logical(T.dense_block_logical(cfg)))
        elif fam == "encdec":
            out["enc_blocks"] = T.stack_logical(T.encdec_block_logical(cfg))
            out["enc_ln"] = ("p_unsharded",)
            out["blocks"] = T.stack_logical(T.encdec_block_logical(cfg, cross=True))
        elif fam == "ssm":
            out["blocks"] = T.stack_logical(T.mamba_block_logical(cfg))
        elif fam == "hybrid":
            out["blocks"] = T.stack_logical(T.mamba_block_logical(cfg))
            out["shared"] = T.dense_block_logical(cfg)
        return out

    def abstract_params(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_shardings(self, params_shape=None):
        """NamedSharding tree matching abstract/concrete params."""
        params_shape = params_shape or self.abstract_params()
        logical = self.logical()
        return jax.tree.map(
            lambda leaf, ax: self.ctx.sharding(ax, leaf.shape),
            params_shape, logical,
            is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))

    # -- forward (train / prefill) -------------------------------------------

    def forward(self, params, tokens, *, frames=None) -> jax.Array:
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        B, Sq = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], (B, Sq))
        x = T.embed_tokens(params["embed"], tokens, ctx, self.act_dtype)
        fam = cfg.family
        if fam == "dense":
            def body(h, lp, _):
                y, _ = T.dense_block_forward(lp, h, cfg, ctx, rcfg,
                                             positions=positions,
                                             use_kernel=self.use_kernel)
                return y, None
            x, _ = T.scan_blocks(body, x, params["blocks"], rcfg, length=cfg.n_layers)
        elif fam == "moe":
            def body(h, lp, _):
                if "dense" in params["blocks"]:
                    for i in range(cfg.moe_every - 1):
                        dlp = jax.tree.map(lambda a: a[i], lp["dense"])
                        h, _ = T.dense_block_forward(dlp, h, cfg, ctx, rcfg,
                                                     positions=positions,
                                                     use_kernel=self.use_kernel)
                h, _ = T.moe_block_forward(lp["moe"], h, cfg, ctx, rcfg,
                                           positions=positions,
                                           use_kernel=self.use_kernel)
                return h, None
            x, _ = T.scan_blocks(body, x, params["blocks"], rcfg, length=self.n_groups)
        elif fam == "encdec":
            assert frames is not None, "encdec forward needs encoder frames"
            xa = self._encode(params, frames)
            def body(h, lp, _):
                y, _ = T.decoder_xblock_forward(lp, h, cfg, ctx, rcfg,
                                                positions=positions, xa=xa,
                                                use_kernel=self.use_kernel)
                return y, None
            x, _ = T.scan_blocks(body, x, params["blocks"], rcfg, length=cfg.n_layers)
        elif fam == "ssm":
            def body(h, lp, _):
                y, _ = T.mamba_block_forward(lp, h, cfg, ctx, variant="mamba1",
                                             use_kernel=self.use_kernel)
                return y, None
            x, _ = T.scan_blocks(body, x, params["blocks"], rcfg, length=cfg.n_layers)
        elif fam == "hybrid":
            x = self._hybrid_forward(params, x, positions)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        w_un = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if cfg.tie_embeddings:
            logits = matmul_param(x, jnp.swapaxes(param_value(w_un, x.dtype), 0, 1))
            return ctx.constrain(logits, "batch", "seq_attn", "vocab")
        return T.unembed(x, w_un, ctx, use_kernel=self.use_kernel)

    def _encode(self, params, frames):
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        B, Se, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(Se)[None, :], (B, Se))
        xa = frames.astype(self.act_dtype)
        xa = ctx.constrain(xa, "batch", "seq", None)
        def body(h, lp, _):
            y, _ = T.dense_block_forward(lp, h, cfg, ctx, rcfg, positions=pos,
                                         causal=False, use_kernel=self.use_kernel)
            return y, None
        xa, _ = T.scan_blocks(body, xa, params["enc_blocks"], rcfg,
                              length=cfg.n_enc_layers)
        return rmsnorm(xa, params["enc_ln"], cfg.norm_eps)

    def _hybrid_forward(self, params, x, positions):
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        chunks = self._hybrid_chunks()
        off = 0
        shared_fwd = T.dense_block_forward
        if rcfg.remat == "block":
            shared_fwd = jax.checkpoint(shared_fwd, static_argnums=(2, 3, 4))
        for size in chunks:
            x, _ = shared_fwd(params["shared"], x, cfg, ctx, rcfg,
                              positions=positions, use_kernel=self.use_kernel)
            sub = jax.tree.map(lambda a: a[off:off + size], params["blocks"])
            def body(h, lp, _):
                y, _ = T.mamba_block_forward(lp, h, cfg, ctx, variant="mamba2",
                                             use_kernel=self.use_kernel)
                return y, None
            x, _ = T.scan_blocks(body, x, sub, rcfg, length=size)
            off += size
        return x

    # -- loss ------------------------------------------------------------------

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits = self.forward(params, batch["tokens"], frames=batch.get("frames"))
        nll = ce_loss(logits, batch["labels"])
        return nll, {"loss": nll}

    # -- decode ----------------------------------------------------------------

    def _kv_cache(self, batch: int, max_len: int,
                  kv_spec: Optional[QuantSpec] = None):
        # heads-major (B, G, S, Dh): decode einsums contract on the minor
        # axis with (b, g) batch dims — no per-step cache transpose.
        # Quantized caches (DESIGN.md §8) hold byte-wide codes next to a
        # STATIC per-head-dim-channel scale leaf (B, G, 1, Dh); static so
        # quantize-on-write is deterministic and evict -> re-prefill resume
        # stays bit-identical.
        cfg = self.cfg
        G, Dh = cfg.n_kv_heads, cfg.d_head
        if kv_spec is not None:
            cdt = kv_code_dtype(kv_spec)
            return {"k": jnp.zeros((batch, G, max_len, Dh), cdt),
                    "k_scale": jnp.ones((batch, G, 1, Dh), jnp.float32),
                    "v": jnp.zeros((batch, G, max_len, Dh), cdt),
                    "v_scale": jnp.ones((batch, G, 1, Dh), jnp.float32)}
        kdt = _dt(self.rcfg.kv_cache_dtype) if self.rcfg.kv_cache_dtype != "int8" else jnp.bfloat16
        return {"k": jnp.zeros((batch, G, max_len, Dh), kdt),
                "v": jnp.zeros((batch, G, max_len, Dh), kdt)}

    def init_cache(self, batch: int, max_len: int,
                   enc_len: Optional[int] = None,
                   kv_spec="auto") -> Dict[str, Any]:
        """Zero decode cache (stacked over layers/groups).

        enc_len sizes the encdec cross-attention cache (defaults to
        max_len). kv_spec overrides the model's KV-cache format ("auto":
        use ``self.kv_spec``); a quantized spec allocates code+scale
        leaves instead of float K/V (DESIGN.md §8). The override is
        allocation-only (sizing / eval_shape): prefill and decode_step
        reject a cache whose layout disagrees with the model's own
        kv_spec rather than silently casting floats into code leaves.
        """
        cfg = self.cfg
        fam = cfg.family
        spec = self.kv_spec if kv_spec == "auto" else validate_kv_spec(kv_spec)
        if spec is not None and fam == "encdec":
            raise ValueError(
                "quantized KV cache is not supported for encdec: the "
                "legacy one-shot path owns its cross-attention cache "
                "(DESIGN.md §8)")
        def stack(make, n):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *[make() for _ in range(n)])
        mk = lambda: self._kv_cache(batch, max_len, spec)
        cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        if fam == "dense":
            cache["kv"] = stack(mk, cfg.n_layers)
        elif fam == "moe":
            ng = self.n_groups
            cache["kv"] = {"moe": stack(mk, ng)}
            if cfg.moe_every > 1:
                cache["kv"]["dense"] = stack(
                    lambda: stack(mk, cfg.moe_every - 1), ng)
        elif fam == "encdec":
            cache["kv"] = stack(mk, cfg.n_layers)
            cache["cross"] = stack(lambda: self._kv_cache(batch, enc_len or max_len),
                                   cfg.n_layers)
            cache["xlen"] = jnp.zeros((), jnp.int32)
        elif fam == "ssm":
            cache["ssm"] = stack(lambda: S.mamba1_init_cache(cfg, batch), cfg.n_layers)
        elif fam == "hybrid":
            cache["ssm"] = stack(lambda: S.mamba2_init_cache(cfg, batch), cfg.n_layers)
            cache["shared_kv"] = stack(mk, len(self._hybrid_chunks()))
        return cache

    def cache_logical(self) -> Dict[str, Any]:
        """Logical axes for every cache leaf (seq-sharded KV for decode).

        Quantized caches add per-head-dim-channel scale leaves; codes keep
        the float leaves' kv_seq sharding (the flash-decode combine over a
        sequence-sharded cache works on codes exactly as on floats). The
        head axis is named "kv_heads_c": unmapped on production meshes
        (kv_seq sharding wins there) but sharded by the serving TP mesh
        (DESIGN.md §9), where codes AND their static scales split along the
        same head axis as the attention weights.
        """
        cfg = self.cfg
        fam = cfg.family
        kv = {"k": ("layers", "batch", "kv_heads_c", "kv_seq", "head_dim"),
              "v": ("layers", "batch", "kv_heads_c", "kv_seq", "head_dim")}
        if self.kv_spec is not None:
            kv["k_scale"] = ("layers", "batch", "kv_heads_c", None, "head_dim")
            kv["v_scale"] = ("layers", "batch", "kv_heads_c", None, "head_dim")
        out: Dict[str, Any] = {"pos": ()}
        if fam == "dense":
            out["kv"] = kv
        elif fam == "moe":
            out["kv"] = {"moe": kv}
            if cfg.moe_every > 1:
                dense_kv = {
                    "k": ("layers", "layers2", "batch", "kv_heads_c", "kv_seq", "head_dim"),
                    "v": ("layers", "layers2", "batch", "kv_heads_c", "kv_seq", "head_dim")}
                if self.kv_spec is not None:
                    dense_kv["k_scale"] = ("layers", "layers2", "batch",
                                           "kv_heads_c", None, "head_dim")
                    dense_kv["v_scale"] = ("layers", "layers2", "batch",
                                           "kv_heads_c", None, "head_dim")
                out["kv"]["dense"] = dense_kv
        elif fam == "encdec":
            out["kv"] = {"k": kv["k"], "v": kv["v"]}
            out["cross"] = {"k": kv["k"], "v": kv["v"]}
            out["xlen"] = ()
        elif fam == "ssm":
            out["ssm"] = {"conv": ("layers", "batch", "conv", "d_inner"),
                          "ssm": ("layers", "batch", "d_inner", "state")}
        elif fam == "hybrid":
            out["ssm"] = {"conv": ("layers", "batch", "conv", "d_inner2"),
                          "ssm": ("layers", "batch", "heads_r", None, "state")}
            out["shared_kv"] = kv
        return out

    # -- paged decode cache (DESIGN.md §10) -----------------------------------

    def _paged_pool(self, n_pages: int, page_size: int,
                    spec: Optional[QuantSpec]):
        # One layer's page pool: a flat (n_pages, G, ps, Dh) array of
        # fixed-size token pages (heads-major within the page, so the
        # decode einsums see the dense cache's layout after gather) plus —
        # quantized — ONE static per-channel scale leaf per layer, global
        # across pages: pages are shareable between requests only because
        # every page quantizes under the same grid (paging.py).
        cfg = self.cfg
        G, Dh = cfg.n_kv_heads, cfg.d_head
        if spec is not None:
            cdt = kv_code_dtype(spec)
            return {"k": jnp.zeros((n_pages, G, page_size, Dh), cdt),
                    "k_scale": jnp.ones((G, 1, Dh), jnp.float32),
                    "v": jnp.zeros((n_pages, G, page_size, Dh), cdt),
                    "v_scale": jnp.ones((G, 1, Dh), jnp.float32)}
        kdt = _dt(self.rcfg.kv_cache_dtype) \
            if self.rcfg.kv_cache_dtype != "int8" else jnp.bfloat16
        return {"k": jnp.zeros((n_pages, G, page_size, Dh), kdt),
                "v": jnp.zeros((n_pages, G, page_size, Dh), kdt)}

    def _require_pageable(self) -> None:
        if self.cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged KV cache supports attention-only families "
                f"(dense/moe), not {self.cfg.family!r}: SSM recurrent "
                "state is O(1) per sequence (nothing to page) and cannot "
                "be position-shared, and encdec serves on the legacy "
                "one-shot path (DESIGN.md §10)")

    def init_paged_cache(self, batch: int, max_len: int, *, n_pages: int,
                         page_size: int) -> Dict[str, Any]:
        """Paged decode cache: page pools + per-slot block tables.

        Layout mirrors ``init_cache`` except the batch*seq cache axes are
        replaced by one flat ``n_pages`` pool axis shared by every slot;
        ``pages`` is the (batch, ceil(max_len/page_size)) block table of
        physical page ids (garbage-page 0 when unallocated) the host-side
        ``launch.paging.PagedKVManager`` maintains, and ``pos`` is the
        dense engine's per-slot length vector unchanged.
        """
        self._require_pageable()
        cfg = self.cfg
        ps = int(page_size)
        if ps < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        max_pages = -(-int(max_len) // ps)
        spec = self.kv_spec

        def stack(make, n):
            return jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[make() for _ in range(n)])
        mk = lambda: self._paged_pool(n_pages, ps, spec)
        cache: Dict[str, Any] = {
            "pos": jnp.zeros((batch,), jnp.int32),
            "pages": jnp.zeros((batch, max_pages), jnp.int32),
        }
        if cfg.family == "dense":
            cache["kv"] = stack(mk, cfg.n_layers)
        else:                                   # moe
            ng = self.n_groups
            cache["kv"] = {"moe": stack(mk, ng)}
            if cfg.moe_every > 1:
                cache["kv"]["dense"] = stack(
                    lambda: stack(mk, cfg.moe_every - 1), ng)
        return cache

    def paged_cache_logical(self) -> Dict[str, Any]:
        """Logical axes for the paged cache: the pool's head axis keeps the
        dense cache's "kv_heads_c" name, so serving-TP sharding (DESIGN.md
        §9) splits pages and their global scales along heads exactly as it
        splits the dense cache; pool/page axes and the block tables
        replicate (every device resolves the same page ids)."""
        self._require_pageable()
        cfg = self.cfg
        kv = {"k": ("layers", "kv_pages", "kv_heads_c", "page_tok",
                    "head_dim"),
              "v": ("layers", "kv_pages", "kv_heads_c", "page_tok",
                    "head_dim")}
        if self.kv_spec is not None:
            kv["k_scale"] = ("layers", "kv_heads_c", None, "head_dim")
            kv["v_scale"] = ("layers", "kv_heads_c", None, "head_dim")
        out: Dict[str, Any] = {"pos": (), "pages": ()}
        if cfg.family == "dense":
            out["kv"] = kv
        else:
            out["kv"] = {"moe": kv}
            if cfg.moe_every > 1:
                out["kv"]["dense"] = {
                    name: (ax[0], "layers2") + ax[1:]
                    for name, ax in kv.items()}
        return out

    def _paged_page_size(self, cache) -> int:
        kv = cache["kv"]["moe"] if "moe" in cache["kv"] else cache["kv"]
        return int(kv["k"].shape[-2])

    def prefill_paged(self, params, tokens, *, cache, slot, length,
                      prefix_len: int = 0):
        """Admission prefill through the page pool (batch 1, one slot).

        ``tokens`` (1, S) is the context *suffix* — the part not already
        resident in shared pages — right-padded to a bucket when S exceeds
        the true ``length`` (scalar, traced). ``prefix_len`` (static: it
        sets gather sizes and the attention bias offset) counts the shared
        resident tokens; the suffix attends to [prefix ; suffix] with the
        kv-chunk boundaries a dense prefill of the whole context would
        use, so the sampled logits match the dense engine's, and writes
        its codes through slot's block-table row. Sets ``pos[slot]`` to
        ``prefix_len + length``. Returns (cache, last-token logits).
        """
        self._require_pageable()
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        self._check_cache_layout(cache)
        B, Sq = tokens.shape
        positions = prefix_len + jnp.broadcast_to(jnp.arange(Sq)[None, :],
                                                  (B, Sq))
        x = T.embed_tokens(params["embed"], tokens, ctx, self.act_dtype)
        row = jnp.take(cache["pages"], slot, axis=0)
        ps = self._paged_page_size(cache)
        kv_spec = self.kv_spec
        pp = lambda lc: dict(pool=lc, row=row, prefix_len=prefix_len,
                             page_size=ps)
        if cfg.family == "dense":
            def body(h, lp, lc):
                return T.dense_block_forward(lp, h, cfg, ctx, rcfg,
                                             positions=positions,
                                             use_kernel=self.use_kernel,
                                             kv_spec=kv_spec,
                                             paged_prefill=pp(lc))
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=cache["kv"],
                                      length=cfg.n_layers)
        else:                                   # moe
            def body(h, lp, lc):
                new_c = dict(lc)
                if "dense" in params["blocks"]:
                    pools = []
                    for i in range(cfg.moe_every - 1):
                        dlp = jax.tree.map(lambda a: a[i], lp["dense"])
                        dlc = jax.tree.map(lambda a: a[i], lc["dense"])
                        h, pool = T.dense_block_forward(
                            dlp, h, cfg, ctx, rcfg, positions=positions,
                            use_kernel=self.use_kernel, kv_spec=kv_spec,
                            paged_prefill=pp(dlc))
                        pools.append(pool)
                    new_c["dense"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *pools)
                h, pool = T.moe_block_forward(lp["moe"], h, cfg, ctx, rcfg,
                                              positions=positions,
                                              use_kernel=self.use_kernel,
                                              kv_spec=kv_spec,
                                              paged_prefill=pp(lc["moe"]))
                new_c["moe"] = pool
                return h, new_c
            blocks_cache = {"moe": cache["kv"]["moe"]}
            if "dense" in cache["kv"]:
                blocks_cache["dense"] = cache["kv"]["dense"]
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=blocks_cache,
                                      length=self.n_groups)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        length = jnp.asarray(length, jnp.int32)
        last = jnp.take_along_axis(
            x, jnp.reshape(length - 1, (B, 1, 1)), axis=1)[:, 0]
        w_un = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if cfg.tie_embeddings:
            logits = matmul_param(
                last, jnp.swapaxes(param_value(w_un, x.dtype), 0, 1))
        else:
            logits = matmul_param(last, w_un, use_kernel=self.use_kernel)
        cache = dict(cache, kv=new_kv,
                     pos=cache["pos"].at[slot].set(prefix_len + length))
        return cache, logits

    def _check_cache_layout(self, cache) -> None:
        # A cache allocated under a different kv_spec than the model's
        # (init_cache(kv_spec=...) is an allocation override only) would
        # silently astype float K/V into int8 code leaves — or attend to
        # raw codes as if they were floats. Runs at trace time.
        kv = cache.get("kv") if "kv" in cache else cache.get("shared_kv")
        if not isinstance(kv, dict):
            return
        if "k" not in kv:                     # moe nests {"moe": ..., ...}
            kv = kv.get("moe", {})
            if "k" not in kv:
                return
        quant = "k_scale" in kv
        if (self.kv_spec is not None) != quant:
            raise ValueError(
                f"cache layout disagrees with the model's kv_spec="
                f"{self.kv_spec!r}: the cache "
                f"{'has' if quant else 'lacks'} scale leaves (was it "
                "allocated by init_cache(kv_spec=...) with a different "
                "format?)")
        if quant and kv["k"].dtype != kv_code_dtype(self.kv_spec):
            raise ValueError(
                f"cache code dtype {kv['k'].dtype} does not match the "
                f"model's kv_spec={self.kv_spec!r} "
                f"(expects {jnp.dtype(kv_code_dtype(self.kv_spec)).name})")

    def cache_shardings(self, batch: int, max_len: int):
        abstract = jax.eval_shape(lambda: self.init_cache(batch, max_len))
        logical = self.cache_logical()
        return jax.tree.map(
            lambda leaf, ax: self.ctx.sharding(ax, leaf.shape),
            abstract, logical,
            is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))

    # -- serving tensor parallelism (DESIGN.md §9) ----------------------------

    @property
    def tp_size(self) -> int:
        """Devices on the serving TP mesh (1 = single-device serving)."""
        return self.ctx.axis_size("tp")

    def manual_tp(self) -> "LM":
        """Twin of this model for use INSIDE a shard_map over the TP mesh:
        constrain no-ops, ``ctx.psum`` is live, and every weight/cache leaf
        the twin sees is the local shard."""
        from .sharding import manual_tp_ctx
        return dataclasses.replace(self, ctx=manual_tp_ctx())

    def param_tp_specs(self, params):
        """PartitionSpec tree (QuantizedTensor-shaped at quantized leaves)
        for the serving TP mesh; raises on indivisible/incongruent leaves."""
        from .sharding import shard_policy_params
        return shard_policy_params(params, self.logical(), self.ctx)

    def cache_tp_specs(self, cache):
        """PartitionSpec tree for a decode cache on the serving TP mesh
        (head-sharded codes AND scales; everything else replicated).
        Detects the paged layout by its block-table leaf."""
        from .sharding import logical_specs
        logical = (self.paged_cache_logical() if "pages" in cache
                   else self.cache_logical())
        return logical_specs(self.ctx, logical, cache)

    def prefill(self, params, tokens, *, cache, frames=None, length=None):
        """Run the full prompt, filling the cache. Returns (cache, last_logits).

        Implemented as forward + cache writes per layer; decode-shape dry-run
        only lowers decode_step, so prefill stays straightforward (chunked
        attention still applies).

        ``length`` (scalar or (B,) int) marks the true prompt length when
        ``tokens`` is right-padded to a bucket (the serving engine pads to
        limit prefill recompilation): logits are gathered at ``length-1``
        and ``cache["pos"]`` becomes the per-sequence length, so the junk
        KV written for pad positions sits beyond every slot's valid prefix
        and is masked (then progressively overwritten) during decode.
        Attention-family models only — a right-padded prompt would pollute
        an SSM recurrent state, which has no per-position mask.
        """
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        self._check_cache_layout(cache)
        B, Sq = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], (B, Sq))
        x = T.embed_tokens(params["embed"], tokens, ctx, self.act_dtype)
        fam = cfg.family
        max_len = _cache_len(cache)
        kv_spec = self.kv_spec

        def scales_of(layer_cache):
            # quantized cache: hand the layer's static scales to the block
            # so prefill fake-quantizes K/V through the cache grid before
            # attending (bit-identical evict -> re-prefill resume).
            if kv_spec is None or "k_scale" not in layer_cache:
                return None
            return {"k_scale": layer_cache["k_scale"],
                    "v_scale": layer_cache["v_scale"]}

        def write_kv(layer_cache, new_kv):
            # grouped (B, S, G, Dh) -> heads-major cache (B, G, S, Dh);
            # quantized caches receive codes (same layout, code dtype) and
            # keep their scale leaves untouched.
            out = dict(layer_cache)
            for name in ("k", "v"):
                dst = layer_cache[name]
                upd = jnp.swapaxes(new_kv[name], 1, 2).astype(dst.dtype)
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    dst, upd, 0, axis=2)
            return out

        if fam == "dense":
            def body(h, lp, lc):
                y, kv = T.dense_block_forward(lp, h, cfg, ctx, rcfg,
                                              positions=positions,
                                              use_kernel=self.use_kernel,
                                              kv_spec=kv_spec,
                                              kv_scales=scales_of(lc))
                return y, write_kv(lc, kv)
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=cache["kv"], length=cfg.n_layers)
            cache = dict(cache, kv=new_kv)
        elif fam == "moe":
            def body(h, lp, lc):
                new_c = dict(lc)
                if "dense" in params["blocks"]:
                    writes = []
                    for i in range(cfg.moe_every - 1):
                        dlp = jax.tree.map(lambda a: a[i], lp["dense"])
                        dlc = jax.tree.map(lambda a: a[i], lc["dense"])
                        h, kv = T.dense_block_forward(dlp, h, cfg, ctx, rcfg,
                                                      positions=positions,
                                                      use_kernel=self.use_kernel,
                                                      kv_spec=kv_spec,
                                                      kv_scales=scales_of(dlc))
                        writes.append(write_kv(dlc, kv))
                    new_c["dense"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *writes)
                h, kv = T.moe_block_forward(lp["moe"], h, cfg, ctx, rcfg,
                                            positions=positions,
                                            use_kernel=self.use_kernel,
                                            kv_spec=kv_spec,
                                            kv_scales=scales_of(lc["moe"]))
                new_c["moe"] = write_kv(lc["moe"], kv)
                return h, new_c
            blocks_cache = {"moe": cache["kv"]["moe"]}
            if "dense" in cache["kv"]:
                blocks_cache["dense"] = cache["kv"]["dense"]
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=blocks_cache, length=self.n_groups)
            cache = dict(cache, kv=new_kv)
        elif fam == "encdec":
            assert frames is not None
            xa = self._encode(params, frames)
            def body(h, lp, lc):
                y, kv = T.decoder_xblock_forward(lp, h, cfg, ctx, rcfg,
                                                 positions=positions, xa=xa,
                                                 use_kernel=self.use_kernel)
                # also record cross-attn k/v once (static thereafter)
                from .attention import attn_tp_mode
                G, Dh = cfg.n_kv_heads, cfg.d_head
                xk = matmul_param(xa, lp["xattn"]["wk"]).reshape(xa.shape[0], -1, G, Dh)
                xv = matmul_param(xa, lp["xattn"]["wv"]).reshape(xa.shape[0], -1, G, Dh)
                new_c = {"self": write_kv(lc["self"], kv),
                         "cross": write_kv(lc["cross"], {"k": xk, "v": xv})}
                return y, new_c
            x, new_c = T.scan_blocks(body, x, params["blocks"], rcfg,
                                     cache={"self": cache["kv"], "cross": cache["cross"]},
                                     length=cfg.n_layers)
            cache = dict(cache, kv=new_c["self"], cross=new_c["cross"],
                         xlen=jnp.asarray(frames.shape[1], jnp.int32))
        elif fam == "ssm":
            def body(h, lp, lc):
                y, nc = T.mamba_block_forward(lp, h, cfg, ctx, cache=lc,
                                              variant="mamba1",
                                              use_kernel=self.use_kernel)
                return y, nc
            x, new_ssm = T.scan_blocks(body, x, params["blocks"], rcfg,
                                       cache=cache["ssm"], length=cfg.n_layers)
            cache = dict(cache, ssm=new_ssm)
        elif fam == "hybrid":
            x, cache = self._hybrid_prefill(params, x, positions, cache, write_kv)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        w_un = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if length is None:
            last = x[:, -1]
            pos = jnp.asarray(tokens.shape[1], jnp.int32)
        else:
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "bucketed prefill (length=) is attention-family only: "
                    "right-padding pollutes the SSM recurrent state")
            pos = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
            last = jnp.take_along_axis(x, (pos - 1)[:, None, None], axis=1)[:, 0]
        if cfg.tie_embeddings:
            logits = matmul_param(last, jnp.swapaxes(param_value(w_un, x.dtype), 0, 1))
        else:
            logits = matmul_param(last, w_un, use_kernel=self.use_kernel)
        cache["pos"] = pos
        return cache, logits

    def _hybrid_prefill(self, params, x, positions, cache, write_kv):
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        chunks = self._hybrid_chunks()
        off = 0
        shared_new = []
        ssm_new = []
        for ci, size in enumerate(chunks):
            lc = jax.tree.map(lambda a: a[ci], cache["shared_kv"])
            kv_scales = None
            if self.kv_spec is not None and "k_scale" in lc:
                kv_scales = {"k_scale": lc["k_scale"],
                             "v_scale": lc["v_scale"]}
            x, kv = T.dense_block_forward(params["shared"], x, cfg, ctx, rcfg,
                                          positions=positions,
                                          use_kernel=self.use_kernel,
                                          kv_spec=self.kv_spec,
                                          kv_scales=kv_scales)
            shared_new.append(write_kv(lc, kv))
            sub = jax.tree.map(lambda a: a[off:off + size], params["blocks"])
            subc = jax.tree.map(lambda a: a[off:off + size], cache["ssm"])
            def body(h, lp, lcc):
                y, nc = T.mamba_block_forward(lp, h, cfg, ctx, cache=lcc,
                                              variant="mamba2",
                                              use_kernel=self.use_kernel)
                return y, nc
            x, new_sub = T.scan_blocks(body, x, sub, rcfg, cache=subc, length=size)
            ssm_new.append(new_sub)
            off += size
        cache = dict(cache)
        cache["shared_kv"] = jax.tree.map(lambda *xs: jnp.stack(xs), *shared_new)
        cache["ssm"] = jax.tree.map(lambda *xs: jnp.concatenate(xs), *ssm_new)
        return x, cache

    def decode_step(self, params, cache, tokens):
        """One decode step. tokens: (B, 1). Returns (new_cache, logits (B, V)).

        ``cache["pos"]`` is a scalar (uniform batch) or a (B,) array of
        per-slot lengths (continuous batching); rotary positions, the KV
        write position and the attention valid-mask all follow it per slot.
        """
        cfg, rcfg, ctx = self.cfg, self.rcfg, self.ctx
        self._check_cache_layout(cache)
        B = tokens.shape[0]
        pos = cache["pos"]
        positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (B, 1))
        x = T.embed_tokens(params["embed"], tokens, ctx, self.act_dtype)
        fam = cfg.family
        kv_kw = dict(kv_spec=self.kv_spec, kv_kernel=self.kv_use_kernel)
        if "pages" in cache:
            # paged decode (DESIGN.md §10): blocks read/write the page pool
            # through the per-slot block tables instead of per-slot rows
            self._require_pageable()
            kv_kw.update(pages=cache["pages"],
                         page_size=self._paged_page_size(cache))
        new_cache = dict(cache, pos=pos + 1)
        if fam == "dense":
            def body(h, lp, lc):
                y, kv = T.dense_block_forward(lp, h, cfg, ctx, rcfg,
                                              positions=positions, cache=lc,
                                              cache_pos=pos,
                                              use_kernel=self.use_kernel,
                                              **kv_kw)
                return y, kv
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=cache["kv"], length=cfg.n_layers)
            new_cache["kv"] = new_kv
        elif fam == "moe":
            def body(h, lp, lc):
                new_c = dict(lc)
                if "dense" in params["blocks"]:
                    kvs = []
                    for i in range(cfg.moe_every - 1):
                        dlp = jax.tree.map(lambda a: a[i], lp["dense"])
                        dlc = jax.tree.map(lambda a: a[i], lc["dense"])
                        h, kv = T.dense_block_forward(dlp, h, cfg, ctx, rcfg,
                                                      positions=positions,
                                                      cache=dlc, cache_pos=pos,
                                                      use_kernel=self.use_kernel,
                                                      **kv_kw)
                        kvs.append(kv)
                    new_c["dense"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *kvs)
                h, kv = T.moe_block_forward(lp["moe"], h, cfg, ctx, rcfg,
                                            positions=positions, cache=lc["moe"],
                                            cache_pos=pos,
                                            use_kernel=self.use_kernel,
                                            **kv_kw)
                new_c["moe"] = kv
                return h, new_c
            blocks_cache = {"moe": cache["kv"]["moe"]}
            if "dense" in cache["kv"]:
                blocks_cache["dense"] = cache["kv"]["dense"]
            x, new_kv = T.scan_blocks(body, x, params["blocks"], rcfg,
                                      cache=blocks_cache, length=self.n_groups)
            new_cache["kv"] = new_kv
        elif fam == "encdec":
            def body(h, lp, lc):
                merged = {"k": lc["self"]["k"], "v": lc["self"]["v"],
                          "xk": lc["cross"]["k"], "xv": lc["cross"]["v"],
                          "xlen": cache["xlen"]}
                y, kv = T.decoder_xblock_forward(lp, h, cfg, ctx, rcfg,
                                                 positions=positions,
                                                 cache=merged, cache_pos=pos,
                                                 use_kernel=self.use_kernel)
                return y, {"self": kv, "cross": lc["cross"]}
            x, new_c = T.scan_blocks(body, x, params["blocks"], rcfg,
                                     cache={"self": cache["kv"], "cross": cache["cross"]},
                                     length=cfg.n_layers)
            new_cache["kv"] = new_c["self"]
            new_cache["cross"] = new_c["cross"]
        elif fam == "ssm":
            def body(h, lp, lc):
                y, nc = T.mamba_block_forward(lp, h, cfg, ctx, cache=lc,
                                              variant="mamba1",
                                              use_kernel=self.use_kernel)
                return y, nc
            x, new_ssm = T.scan_blocks(body, x, params["blocks"], rcfg,
                                       cache=cache["ssm"], length=cfg.n_layers)
            new_cache["ssm"] = new_ssm
        elif fam == "hybrid":
            chunks = self._hybrid_chunks()
            off = 0
            shared_new, ssm_new = [], []
            for ci, size in enumerate(chunks):
                lc = jax.tree.map(lambda a: a[ci], cache["shared_kv"])
                x, kv = T.dense_block_forward(params["shared"], x, cfg, ctx, rcfg,
                                              positions=positions, cache=lc,
                                              cache_pos=pos,
                                              use_kernel=self.use_kernel,
                                              **kv_kw)
                shared_new.append(kv)
                sub = jax.tree.map(lambda a: a[off:off + size], params["blocks"])
                subc = jax.tree.map(lambda a: a[off:off + size], cache["ssm"])
                def body(h, lp, lcc):
                    y, nc = T.mamba_block_forward(lp, h, cfg, ctx, cache=lcc,
                                                  variant="mamba2",
                                                  use_kernel=self.use_kernel)
                    return y, nc
                x, new_sub = T.scan_blocks(body, x, sub, rcfg, cache=subc, length=size)
                ssm_new.append(new_sub)
                off += size
            new_cache["shared_kv"] = jax.tree.map(lambda *xs: jnp.stack(xs), *shared_new)
            new_cache["ssm"] = jax.tree.map(lambda *xs: jnp.concatenate(xs), *ssm_new)
        x = rmsnorm(x[:, 0], params["ln_f"], cfg.norm_eps)
        w_un = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if cfg.tie_embeddings:
            logits = matmul_param(x, jnp.swapaxes(param_value(w_un, x.dtype), 0, 1))
        else:
            logits = matmul_param(x, w_un, use_kernel=self.use_kernel)
        return new_cache, self.ctx.constrain(logits, "batch", "vocab")


def _cache_len(cache) -> int:
    if "kv" in cache:
        leaf = cache["kv"]["moe"]["k"] if isinstance(cache["kv"], dict) and "moe" in cache["kv"] \
            else cache["kv"]["k"]
        return leaf.shape[2]
    return 0


def kv_decode_bytes_per_token(cfg: ModelConfig, context_len: int,
                              kv_spec: Optional[QuantSpec] = None,
                              cache_dtype_bytes: int = 2) -> Dict[str, float]:
    """Modeled HBM bytes read from the KV cache per decoded token.

    Every decode step re-reads each attention layer's full valid K+V prefix
    — the S-proportional term that bounds decode throughput at long context
    (benchmarks/bench_roofline.py). Quantized caches stream byte-wide codes
    (``code_bytes``) plus an S-independent per-step scale read
    (``scale_bytes``: (B-slot share) 2 * G * Dh * 4 per layer, VMEM-resident
    in the fused kernel and negligible at depth); bf16 caches stream
    ``cache_dtype_bytes`` per element and no scales. pofx codes occupy one
    byte per element in HBM even though only N-1 bits carry information —
    bit-packing them is headroom this model does not claim (DESIGN.md §8).
    """
    fam = cfg.family
    if fam == "ssm":
        n_attn = 0
    elif fam == "hybrid":
        n_attn = -(-cfg.n_layers // cfg.attn_every) if cfg.attn_every else 0
    else:  # dense / moe / encdec self-attention layers
        n_attn = cfg.n_layers
    G, Dh = cfg.n_kv_heads, cfg.d_head
    per_elem = 1 if kv_spec is not None else cache_dtype_bytes
    return {
        "code_bytes": float(n_attn * 2 * G * context_len * Dh * per_elem),
        "scale_bytes": float(n_attn * 2 * G * Dh * 4) if kv_spec is not None
        else 0.0,
    }


def build_model(cfg: ModelConfig, rcfg: RunConfig, mesh=None,
                use_kernel: bool = False, kv_spec=None,
                kv_kernel: Optional[bool] = None) -> LM:
    ctx = make_ctx(mesh, sequence_parallel=rcfg.sequence_parallel)
    return LM(cfg, rcfg, ctx, use_kernel=use_kernel, kv_spec=kv_spec,
              kv_kernel=kv_kernel)


# ---------------------------------------------------------------------------
# Post-training quantization of a parameter tree (the paper's technique)
# ---------------------------------------------------------------------------

_NEVER_QUANT = ("ln", "norm", "A_log", "dt_bias", "D", "router", "conv_w",
                "conv_b", "q_norm", "k_norm")


def apply_policy(params, policy):
    """Convert weight matrices to QuantizedTensor storage per a QuantPolicy.

    ``policy`` is a QuantPolicy or policy string (see repro.core.policy).
    Each eligible leaf — a >=2D matmul weight: attention/MLP/MoE/SSM
    projections and embed/unembed — is matched against the policy's ordered
    path-glob rules; the first matching rule's spec decides its format.
    Norm scales, SSM recurrence params, conv taps and MoE router weights are
    never quantized regardless of rules (DESIGN.md §5), as is any leaf no
    rule matches or a "keep" rule claims. fp32/bf16 rules cast in place
    (no QuantizedTensor wrapper — the float fast path stays float).
    """
    if isinstance(policy, str):
        policy = QuantPolicy.from_string(policy)
    return _apply_policy(params, policy)


def _apply_policy(params, policy: QuantPolicy, prefix=(), mapped: int = 0):
    """``apply_policy`` on a subtree found at path ``prefix`` whose first
    ``mapped`` layer-stacking axes are mapped away (``LM.init_quantized``
    quantizes one layer of the stack at a time)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, leaf in flat:
        names = list(prefix) + [str(getattr(k, "key", getattr(k, "name", k)))
                                for k in path]
        name = "/".join(names)
        # layer-stacked leaves must keep per-layer scales (leading dims stay
        # mapped) so lax.scan can slice codes and scale together.
        stack_depth = 0
        if "blocks" in names or "enc_blocks" in names:
            stack_depth = 2 if "dense" in names else 1
        stack_depth -= mapped
        eligible = (leaf.ndim >= 2 + stack_depth
                    and not any(t in name for t in _NEVER_QUANT))
        spec = policy.match(name) if eligible else None
        if spec is None:
            out.append(leaf)
            continue
        if spec.kind in ("fp32", "bf16"):
            dt = jnp.float32 if spec.kind == "fp32" else jnp.bfloat16
            out.append(jnp.asarray(leaf).astype(dt))
            continue
        fn = lambda w: quantize(w, spec, axis=-1)
        for _ in range(stack_depth):
            fn = jax.vmap(fn)
        out.append(fn(jnp.asarray(leaf)))
    return jax.tree_util.tree_unflatten(treedef, out)


def quantize_params(params, spec: QuantSpec, *, quant_embed: bool = True):
    """Back-compat shim: uniform-policy application of one QuantSpec.

    Equivalent to ``apply_policy(params, QuantPolicy.uniform(spec))``, with
    ``quant_embed=False`` expressed as a leading "*embed*=keep" rule.
    """
    rules = (("*embed*", None),) if not quant_embed else ()
    return apply_policy(params, QuantPolicy(rules=rules + (("*", spec),)))


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for the dry-run (no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract batch for one (arch, shape) cell.

    train/prefill: {tokens, labels[, frames]}; decode: {tokens (B,1)} —
    cache/params come from abstract_params / init_cache eval_shape.
    """
    B, Sq = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        spec = {"tokens": jax.ShapeDtypeStruct((B, Sq), i32),
                "labels": jax.ShapeDtypeStruct((B, Sq), i32)}
        if cfg.family == "encdec":
            spec["frames"] = jax.ShapeDtypeStruct((B, Sq, cfg.d_model), jnp.bfloat16)
        return spec
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
