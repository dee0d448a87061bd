"""Plain float32 reference of a dense llama-style decoder with grouped-query
attention, for the configurations that name ``"reference": "dense_gqa"``.

It implements the model the configuration states, from its definition:

- weights made from the seed: a normal draw per matrix, times
  ``fan_in ** -0.5`` (the embedding times 1), rounded to bfloat16; the keys
  are split as ``PRNGKey(seed)`` -> 8 (0 embedding, 1 output head,
  2 layers) -> one per layer -> 2 (attention, MLP) -> 6 (q, k, v, o) and 3
  (gate, up, down);
- stored as pofx8 (``pofx_table``): each output channel is divided by the
  power of two at or above its largest magnitude, rounded to the FxP(8, 7)
  grid, encoded as an 8-bit posit with 2 exponent bits (round to nearest,
  ties to even, on the bit pattern) held to the normalized range [-1, 1),
  and read back by the PoFx converter, which truncates to FxP(8, 7);
- keys and values stored as fxp8: rounded to multiples of 1/128 and held to
  [-1, 127/128] (the cache's static scales are 1);
- RMSNorm with unit weights, rotary embeddings on the two halves of each
  head, causal softmax attention, a SiLU-gated MLP, untied output head.

Everything runs in float32 with matmuls at ``Precision.HIGHEST``, one layer
at a time (the weights are made anew from the seed per layer and dropped),
over the whole token sequence with no cache and no kernels. It imports
nothing of the system under test.

``control=True`` runs the same model with every activation that enters a
matmul rounded to float8 (e4m3): the step below the bfloat16 activations
the configurations state, which the check has to refuse.
"""
from __future__ import annotations

import functools
from fractions import Fraction
from typing import Sequence

import numpy as np


# ---------------------------------------------------------------------------
# The pofx8 weight format, from its definition (exact arithmetic, 256 codes)
# ---------------------------------------------------------------------------

def posit_value(pattern: int, n: int, es: int) -> Fraction:
    """Value of an ``n``-bit posit pattern (0 and NaR both give 0 here)."""
    pattern &= (1 << n) - 1
    if pattern in (0, 1 << (n - 1)):
        return Fraction(0)
    neg = pattern >> (n - 1)
    if neg:
        pattern = (-pattern) & ((1 << n) - 1)
    bits = format(pattern, f"0{n}b")[1:]
    run = len(bits) - len(bits.lstrip(bits[0]))
    k = run - 1 if bits[0] == "1" else -run
    rest = bits[run + 1:]
    exp_bits = (rest[:es] + "0" * es)[:es]
    frac_bits = rest[es:]
    e = int(exp_bits, 2)
    f = Fraction(int(frac_bits, 2), 1 << len(frac_bits)) if frac_bits else 0
    v = Fraction(2) ** ((1 << es) * k + e) * (1 + f)
    return -v if neg else v


def posit_round(x: Fraction, n: int, es: int) -> int:
    """The ``n``-bit posit pattern nearest ``x``: the exact bit string is cut
    after ``n - 1`` bits and rounded to nearest, ties to even."""
    if x == 0:
        return 0
    neg = x < 0
    m = -x if neg else x
    scale = m.numerator.bit_length() - m.denominator.bit_length()
    if Fraction(2) ** scale > m:
        scale -= 1
    k, e = scale >> es, scale & ((1 << es) - 1)
    frac = m / Fraction(2) ** scale - 1
    regime = "1" * (k + 1) + "0" if k >= 0 else "0" * (-k) + "1"
    fbits = ""
    while frac and len(fbits) < 64:
        frac *= 2
        fbits += "1" if frac >= 1 else "0"
        frac -= 1 if frac >= 1 else 0
    s = regime + format(e, f"0{es}b") + fbits
    s = s + "0" * max(0, n - s.__len__())
    body = int(s[:n - 1], 2)
    guard = s[n - 1] == "1"
    sticky = "1" in s[n:]
    if guard and (sticky or body & 1):
        body += 1
    body = min(max(body, 1), (1 << (n - 1)) - 1)
    return ((-body) & ((1 << n) - 1)) if neg else body


def pofx_table(n: int = 8, es: int = 2, m: int = 8) -> np.ndarray:
    """FxP(m, m-1) code served for each FxP(m, m-1) code ``a`` in
    ``[-2^(m-1), 2^(m-1))`` (index ``a + 2^(m-1)``): the posit nearest
    ``a / 2^(m-1)``, held to the normalized range, truncated back."""
    half = 1 << (m - 1)
    out = []
    for a in range(-half, half):
        p = posit_round(Fraction(a, half), n, es)
        signed = p - (1 << n) if p >= 1 << (n - 1) else p
        signed = min(max(signed, -(1 << (n - 2))), (1 << (n - 2)) - 1)
        v = posit_value(signed & ((1 << n) - 1), n, es)
        mag = min(int(abs(v) * half), half - 1)     # floor, then saturate
        out.append(-mag if v < 0 else mag)
    return np.array(out, np.int32)


def served_codes(a):
    """``pofx_table`` for pofx8 (n 8, es 2, m 8) as elementwise arithmetic,
    for use on the device (a table gather per weight is slow on the TPU).
    Magnitudes below 16 are exact; above, the posit keeps 3 fraction bits,
    so a magnitude rounds to a multiple of 2, 4 or 8 (ties to the even
    pattern); 1.0 is held to 120 on the positive side, and -1.0 saturates
    to -127 in the converter."""
    import jax.numpy as jnp
    mag = jnp.abs(a).astype(jnp.float32)
    step = jnp.where(mag < 16, 1.0, jnp.where(mag < 32, 2.0,
                     jnp.where(mag < 64, 4.0, 8.0)))
    r = jnp.round(mag / step) * step
    pos = jnp.minimum(r, 120.0)
    negv = jnp.where(r >= 128, 127.0, r)
    return jnp.where(a < 0, -negv, pos)


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _served_weight(key, in_dim: int, out_dims: tuple, scale: float):
    """(in_dim, prod(out_dims)) float32 values the served model computes with."""
    import jax
    import jax.numpy as jnp
    w = jax.random.normal(key, (in_dim, *out_dims), jnp.float32) * scale
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    mx = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    mant, ex = jnp.frexp(jnp.maximum(mx, 1e-12))
    s = jnp.where(mant == 0.5, jnp.ldexp(jnp.float32(1), ex - 1),
                  jnp.ldexp(jnp.float32(1), ex))
    a = jnp.clip(jnp.round(w / s * 128.0), -128, 127)
    v = served_codes(a) * (1.0 / 128.0) * s
    return v.reshape(in_dim, -1)


def _keys(seed: int, n_layers: int):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return ks[0], ks[1], jax.random.split(ks[2], n_layers)


# ---------------------------------------------------------------------------
# The forward pass, one layer per call
# ---------------------------------------------------------------------------

def _mm(x, w):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _act(x, control: bool):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if control else x


def _rms(x, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _fxp8(x):
    import jax.numpy as jnp
    return jnp.clip(jnp.round(x * 128.0), -128, 127) * (1.0 / 128.0)


def _rope(x, theta: float):
    """x (S, heads, Dh) at positions 0..S-1; the two halves rotate."""
    import jax.numpy as jnp
    S, _, Dh = x.shape
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(d: int, H: int, G: int, Dh: int, ff: int, theta: float,
              eps: float, modes: tuple):
    import jax
    import jax.numpy as jnp

    def attend(x, wq, wk, wv, control):
        S = x.shape[0]
        q = _rope(_mm(x, wq).reshape(S, H, Dh), theta)
        k = _fxp8(_rope(_mm(x, wk).reshape(S, G, Dh), theta))
        v = _fxp8(_mm(x, wv).reshape(S, G, Dh))
        q = q.reshape(S, G, H // G, Dh)
        sc = jnp.einsum("sgrd,tgd->grst", q, k,
                        precision=jax.lax.Precision.HIGHEST) * Dh ** -0.5
        mask = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("grst,tgd->sgrd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        return _act(o.reshape(S, H * Dh), control)

    def layer(key, hs):
        k1, k2 = jax.random.split(key)
        ka = jax.random.split(k1, 6)
        km = jax.random.split(k2, 3)
        wq = _served_weight(ka[0], d, (H, Dh), d ** -0.5)
        wk = _served_weight(ka[1], d, (G, Dh), d ** -0.5)
        wv = _served_weight(ka[2], d, (G, Dh), d ** -0.5)
        wo = _served_weight(ka[3], H * Dh, (d,), (H * Dh) ** -0.5)
        wg = _served_weight(km[0], d, (ff,), d ** -0.5)
        wu = _served_weight(km[1], d, (ff,), d ** -0.5)
        wd = _served_weight(km[2], ff, (d,), ff ** -0.5)
        out = []
        for h, mode in zip(hs, modes):
            control = mode == "fp8"

            def one(hseq):
                x = _act(_rms(hseq, eps), control)
                hseq = hseq + _mm(attend(x, wq, wk, wv, control), wo)
                x = _act(_rms(hseq, eps), control)
                g = _mm(x, wg)
                u = _mm(x, wu)
                return hseq + _mm(_act(jax.nn.silu(g) * u, control), wd)

            out.append(jax.lax.map(one, h))
        return tuple(out)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _embed_fn(V: int, d: int):
    import jax
    import jax.numpy as jnp

    def embed(key, tokens):
        w = _served_weight(key, V, (d,), 1.0)
        return jnp.take(w, tokens, axis=0)
    return jax.jit(embed)


@functools.lru_cache(maxsize=None)
def _head_fn(d: int, V: int, eps: float, modes: tuple):
    import jax
    import jax.numpy as jnp

    def head(key, hs, rows, served):
        """Gaps at ``rows`` (flat indices into the (N*S) positions) of the
        first mode's logits: of the served token, and of each mode's own
        top token."""
        w = _served_weight(key, d, (V,), d ** -0.5)
        logits = []
        for h, mode in zip(hs, modes):
            x = _act(_rms(h.reshape(-1, d)[rows], eps), mode == "fp8")
            logits.append(_mm(x, w))
        ref = logits[0]
        best = ref.max(axis=-1)
        gap_served = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
        gaps_top = [best - jnp.take_along_axis(
            ref, jnp.argmax(lg, axis=-1)[:, None], 1)[:, 0] for lg in logits]
        return gap_served, jnp.stack(gaps_top)
    return jax.jit(head)


def logit_gaps(config: dict, seed: int, prompts: Sequence[np.ndarray],
               served: Sequence[np.ndarray], pad_to: int,
               control: bool = False) -> dict:
    """How far below the reference's best logit each served token lies.

    ``prompts[i]`` and ``served[i]`` are a request's prompt and the tokens
    the system served for it; the reference runs over the prompt and all
    but the last served token, and reads, at the position before each
    served token, ``max(logits) - logits[served token]``. With ``control``
    it also reads the same gap for the token the float8 control puts
    first. Sequences are right-padded to a multiple of ``pad_to``.
    """
    import jax
    import jax.numpy as jnp
    d, H = config["hidden_size"], config["num_attention_heads"]
    G, Dh = config["num_key_value_heads"], config["head_dim"]
    ff, V = config["intermediate_size"], config["vocab_size"]
    L = config["num_hidden_layers"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    modes = ("f32", "fp8") if control else ("f32",)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(s, np.int32)[:-1]])
            for p, s in zip(prompts, served)]
    S = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    tokens = np.zeros((len(seqs), S), np.int32)
    rows, want = [], []
    for i, (p, s, seq) in enumerate(zip(prompts, served, seqs)):
        tokens[i, :len(seq)] = seq
        rows.extend(i * S + len(p) - 1 + np.arange(len(s)))
        want.extend(np.asarray(s, np.int32))
    k_embed, k_head, k_layers = _keys(seed & 0xFFFFFFFF, L)
    h = _embed_fn(V, d)(k_embed, jnp.asarray(tokens))
    hs = tuple(h for _ in modes)
    layer = _layer_fn(d, H, G, Dh, ff, theta, eps, modes)
    for l in range(L):
        hs = layer(k_layers[l], hs)
    gap_served, gaps_top = _head_fn(d, V, eps, modes)(
        k_head, hs, jnp.asarray(np.array(rows, np.int32)),
        jnp.asarray(np.array(want, np.int32)))
    gap_served = np.asarray(gap_served)
    out = {"gap": float(gap_served.max()), "n_tokens": int(len(rows)),
           "gaps": gap_served}
    if control:
        out["control_gap"] = float(np.asarray(gaps_top)[1].max())
    return out
