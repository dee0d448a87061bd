"""The weight matmuls of one decode step of a configuration, and the model
FLOPs of a decoded token, counted from the configuration file's published
keys (through ``bench/cell.py::model_fields``, the benchmark's own key
table; the system's ``ModelConfig`` is never read).

Notation, with the key each symbol is read from: d ``hidden_size``; H, G
``num_attention_heads``, ``num_key_value_heads``; Dh ``head_dim``; f
``intermediate_size``; V ``vocab_size``; L ``num_hidden_layers``; E the
experts held (``num_local_experts`` / ``n_routed_experts`` /
``num_experts``); k ``num_experts_per_tok``; s ``n_shared_experts``; fe
the expert width (``moe_intermediate_size``, else f); N the state size and
di the inner width of a state-space layer (``state_size``,
``expand`` x d, ...; see the table in ``cell.py``).

A published key that no ``ModelConfig`` field holds yet sits under the
file's ``"published"`` object, and is read from there: the expert width
``moe_intermediate_size``, the leading dense layers, and the window
(``sliding_window``, ``use_sliding_window``, ``layer_types``). The count is
of the published model.

Per layer kind, the weight matmuls a decoded token passes through, as
(k, n):

- attention: q (d, H.Dh), k and v (d, G.Dh), o (H.Dh, d);
- dense MLP: gate and up (d, f), down (f, d) (a gated activation), or up
  (d, f) and down (f, d);
- expert MLP: the router (d, E), then each of the k routed experts' MLP at
  width fe, then the s shared experts as one MLP of width s.fe;
- mamba1 (family ``ssm``): in (d, 2.di), x (di, R + 2.N) with R the
  time-step rank ``time_step_rank``, dt (R, di), out (di, d);
- mamba2 (family ``hybrid``): in (d, 2.di + 2.N + di / P) with P the head
  size ``mamba_head_dim``, out (di, d); and a shared attention block with a
  dense MLP before each run of ``attn_every`` mamba2 layers (``"system"``);
- the output head (d, V).

Layout: the first ``first_k_dense_replace`` (or ``num_dense_layers``)
layers are dense and counted once; of the rest, every ``moe_every``-th
(``decoder_sparse_step`` / ``interleave_moe_layer_step``) routes.

Which matmuls run in ``pofx_matmul`` (``Matmul.kernel``), as the system
calls them: all of the above but the router (float32 logits), the routed
experts (XLA einsums over decoded weights, ``nn/moe.py``) and mamba1's x
and dt projections (``nn/ssm.py``).
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple


class Matmul(NamedTuple):
    """One weight matmul of a layer, which a token passes through ``times``
    times (a routed expert shape: ``top_k``); ``kernel``: run by
    ``pofx_matmul``."""
    k: int
    n: int
    times: int = 1
    kernel: bool = True


def _fields(config: dict) -> dict:
    from bench.cell import model_fields
    return model_fields(config)


def dims(config: dict) -> dict:
    """The attention sizes the KV kernels' counts read: H, G and Dh."""
    m = _fields(config)
    return dict(H=m["n_heads"], G=m["n_kv_heads"], Dh=m["d_head"])


def _gated(act: str) -> bool:
    """silu and gelu MLPs are gated (gate, up, down); relu2 and gelu_plain
    are not (up, down)."""
    return act in ("silu", "gelu")


def _mlp(d: int, width: int, gated: bool) -> List[Matmul]:
    if gated:
        return [Matmul(d, width), Matmul(d, width), Matmul(width, d)]
    return [Matmul(d, width), Matmul(width, d)]


def _attention(m: dict) -> List[Matmul]:
    d, H, G, Dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    return [Matmul(d, H * Dh), Matmul(d, G * Dh), Matmul(d, G * Dh),
            Matmul(H * Dh, d)]


def n_expert_layers(config: dict) -> Tuple[int, int]:
    """(dense layers, expert layers): the leading dense layers, then every
    ``moe_every``-th of the rest routes."""
    m, pub = _fields(config), config.get("published", {})
    L = m["n_layers"]
    if not m.get("n_experts"):
        return L, 0
    lead = int(pub.get("first_k_dense_replace")
               or pub.get("num_dense_layers") or 0)
    moe = (L - lead) // m.get("moe_every", 1)
    return L - moe, moe


def step_matmuls(config: dict) -> List[Matmul]:
    """Every weight matmul of one decode step, in layer order (dense,
    expert, state-space and shared-attention layers, then the head)."""
    m, pub = _fields(config), config.get("published", {})
    fam, d = m["family"], m["d_model"]
    gated = _gated(m.get("act", "silu"))
    out: List[Matmul] = []
    if fam in ("dense", "moe"):
        n_dense, n_moe = n_expert_layers(config)
        out += (_attention(m) + _mlp(d, m["d_ff"], gated)) * n_dense
        if n_moe:
            E, k = m["n_experts"], m["top_k"]
            fe = int(pub.get("moe_intermediate_size") or m["d_ff"])
            expert = [Matmul(a, b, times=k, kernel=False)
                      for a, b, *_ in _mlp(d, fe, gated)]
            shared = _mlp(d, m.get("n_shared_experts", 0) * fe, gated)
            layer = (_attention(m) + [Matmul(d, E, kernel=False)] + expert
                     + (shared if m.get("n_shared_experts") else []))
            out += layer * n_moe
    elif fam == "ssm":
        di, N, R = m["d_inner"], m["ssm_state"], m["dt_rank"]
        out += [Matmul(d, 2 * di), Matmul(di, R + 2 * N, kernel=False),
                Matmul(R, di, kernel=False), Matmul(di, d)] * m["n_layers"]
    elif fam == "hybrid":
        di, N = m["d_inner"], m["ssm_state"]
        nh = di // m.get("ssm_head_dim", 64)
        mamba2 = [Matmul(d, 2 * di + 2 * N + nh), Matmul(di, d)]
        shared = _attention(m) + _mlp(d, m["d_ff"], gated)
        out += shared * n_shared_blocks(config) + mamba2 * m["n_layers"]
    else:
        raise ValueError(f"no work count for family {fam!r}")
    return out + [Matmul(d, m["vocab_size"])]


def n_shared_blocks(config: dict) -> int:
    """Applications of a hybrid's shared attention block: one before each
    run of ``attn_every`` mamba2 layers."""
    m = _fields(config)
    k = m.get("attn_every", 0)
    return -(-m["n_layers"] // k) if k else 0


def decode_matmuls(config: dict) -> List[Tuple[int, int]]:
    """(k, n) of every weight matmul one decoded token passes through, a
    routed expert's once for each of the ``top_k`` experts it takes. A
    dense configuration: q, k, v, o, gate, up and down of each layer, then
    the output head."""
    return [(mm.k, mm.n) for mm in step_matmuls(config)
            for _ in range(mm.times)]


def attention_layers(config: dict) -> List[Tuple[int, int]]:
    """(layers, window) groups of the attention a token runs: a windowed
    layer attends to at most ``window`` positions (published
    ``sliding_window``; the layers ``layer_types`` names
    ``"sliding_attention"``, every layer when it names none), a full one
    has window 0 (no limit)."""
    m, pub = _fields(config), config.get("published", {})
    if m["family"] == "ssm":
        return []
    if m["family"] == "hybrid":
        return [(n_shared_blocks(config), 0)]
    L = m["n_layers"]
    window = pub.get("sliding_window") or 0
    if not window or pub.get("use_sliding_window") is False:
        return [(L, 0)]
    kinds = pub.get("layer_types")
    n_win = L if kinds is None else sum(
        1 for t in kinds[:L] if t == "sliding_attention")
    return [(n, w) for n, w in ((L - n_win, 0), (n_win, window)) if n]


def attended(context: int, window: int) -> int:
    """Positions a token at ``context`` attends to under ``window``."""
    return min(context, window) if window else context


def token_flops(config: dict, context: int) -> float:
    """Model FLOPs of decoding one token that attends to ``context``
    positions: 2 per weight of every matmul it passes through
    (``decode_matmuls``), plus scores and weighted values, 2 * 2 * H * Dh
    per attended position in every attention layer (a windowed one attends
    to min(context, window)), plus each state-space layer's scan: the state
    update and its read-out, 2 * 2 * di * N."""
    m = _fields(config)
    weights = sum(k * n for k, n in decode_matmuls(config))
    attn = 0.0
    for layers, window in attention_layers(config):
        attn += (4.0 * layers * m["n_heads"] * m["d_head"]
                 * attended(context, window))
    scan = 0.0
    if m["family"] in ("ssm", "hybrid"):
        scan = 4.0 * m["n_layers"] * m["d_inner"] * m["ssm_state"]
    return 2.0 * weights + attn + scan
