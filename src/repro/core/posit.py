"""Posit(N, ES) codec — exact, vectorized, for N <= 16.

Implements the posit number system of Gustafson & Yonemoto [39] as used by
ExPAN(N)D: ``value = (-1)^s * (2^(2^ES))^k * 2^e * 1.f`` with two's-complement
handling of negative codes, regime run-length encoding of ``k``, an
MSB-aligned (zero-completed) exponent field, and NaR at ``10...0``.

Two implementations share one generic body:

* ``*_np``  — numpy, float64: the golden reference used by tests/benchmarks.
* jnp path — float32 (exact for N <= 16, ES <= 3: significand has <= 14
  fraction bits and the scale stays within float32 range), jit-friendly,
  no data-dependent control flow (static unrolled bit loops).

Codes are carried as int32 arrays holding the raw N-bit pattern in [0, 2^N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "posit_decode_np",
    "posit_decode",
    "posit_encode_np",
    "posit_encode",
    "posit_value_table",
    "posit_max",
    "posit_min_pos",
    "NAR",
]

# Tables up to this many entries are searched and read with elementwise
# compares and selects instead of gathers. On a TPU v5e a gather of one value
# per element from a small table costs several ns per element (decoding a
# 262M-code table that way took about 2 s), and the searchsorted encode makes
# about ten per element: minutes for a 9B model. Selects run at VPU speed and
# give the same values.
_SELECT_MAX = 256


@functools.partial(jax.jit, static_argnums=(1,))
def _select_chain(idx, table: tuple):
    out = jnp.full(idx.shape, table[0], jnp.float32)
    for j, v in enumerate(table[1:], 1):
        out = jnp.where(idx == j, v, out)
    return out


def _table_lookup(table: np.ndarray, idx) -> jax.Array:
    """``table[idx]`` for a float32 host table; idx must lie in range."""
    if table.shape[0] > _SELECT_MAX:
        return jnp.take(jnp.asarray(table), idx, axis=0)
    return _select_chain(idx, tuple(table.tolist()))


def NAR(N: int) -> int:
    """The Not-a-Real code for an N-bit posit (1 followed by zeros)."""
    return 1 << (N - 1)


def _check_config(N: int, ES: int) -> None:
    if not (2 <= N <= 16):
        raise ValueError(f"posit N={N} unsupported (need 2..16)")
    if not (0 <= ES <= 4):
        raise ValueError(f"posit ES={ES} unsupported (need 0..4)")


def _decode_fields(c, N: int, ES: int, xp):
    """Shared field extraction. Returns (sign_bit, k, e, frac_window).

    ``frac_window`` is the fraction left-aligned in an (N-1)-bit window, i.e.
    fraction value = frac_window / 2^(N-1). Exponent bits cut off by the
    regime are completed with zeros (standard posit semantics).
    """
    c = xp.asarray(c).astype(xp.int32)
    mask_n = (1 << N) - 1
    mask_body = (1 << (N - 1)) - 1
    c = c & mask_n
    s = (c >> (N - 1)) & 1
    # A2: two's complement magnitude pattern for negative codes.
    body = xp.where(s == 1, (-c) & mask_n, c) & mask_body
    # Leading bit of the regime.
    r0 = (body >> (N - 2)) & 1
    # Count of leading bits equal to r0 (unrolled: N is static).
    x = xp.where(r0 == 1, (~body) & mask_body, body)
    m = xp.zeros_like(c)
    found = xp.zeros_like(c, dtype=bool)
    for i in range(N - 2, -1, -1):
        bit = (x >> i) & 1
        found = found | (bit == 1)
        m = m + xp.where(found, 0, 1).astype(xp.int32)
    k = xp.where(r0 == 0, -m, m - 1)
    # Drop sign(implicit)/regime(m)/terminator(1): remaining bits MSB-aligned
    # in the (N-1)-bit window; zeros shift in from the right, which implements
    # zero-completion of truncated exponent/fraction fields.
    aligned = (body << (m + 1)) & mask_body
    if ES > 0:
        e = aligned >> (N - 1 - ES) if (N - 1 - ES) >= 0 else aligned
        frac = (aligned << ES) & mask_body
    else:
        e = xp.zeros_like(c)
        frac = aligned
    return s, k, e, frac


def posit_decode_np(codes, N: int, ES: int) -> np.ndarray:
    """Golden float64 decode. Zero -> 0.0, NaR -> NaN."""
    _check_config(N, ES)
    c = np.asarray(codes).astype(np.int64) & ((1 << N) - 1)
    s, k, e, frac = _decode_fields(c.astype(np.int32), N, ES, np)
    scale = (k.astype(np.int64) << ES) + e
    sig = 1.0 + frac.astype(np.float64) / float(1 << (N - 1))
    val = np.where(s == 1, -1.0, 1.0) * np.exp2(scale.astype(np.float64)) * sig
    val = np.where(c == 0, 0.0, val)
    val = np.where(c == NAR(N), np.nan, val)
    return val


def posit_decode(codes, N: int, ES: int) -> jax.Array:
    """jnp float32 decode (exact for N <= 16); jit/vmap friendly."""
    _check_config(N, ES)
    c = jnp.asarray(codes).astype(jnp.int32) & ((1 << N) - 1)
    s, k, e, frac = _decode_fields(c, N, ES, jnp)
    scale = (k << ES) + e
    sig = 1.0 + frac.astype(jnp.float32) / float(1 << (N - 1))
    # Exact 2^scale: build the float32 bit pattern directly (jnp.exp2 is not
    # correctly rounded for float32). scale stays within normal range for
    # N <= 16, ES <= 3 (|scale| <= 120).
    pow2 = jax.lax.bitcast_convert_type(
        ((scale + 127) << 23).astype(jnp.int32), jnp.float32
    )
    val = jnp.where(s == 1, -1.0, 1.0) * pow2 * sig
    val = jnp.where(c == 0, 0.0, val)
    val = jnp.where(c == NAR(N), jnp.nan, val)
    return val


@functools.lru_cache(maxsize=64)
def posit_value_table(N: int, ES: int) -> np.ndarray:
    """float64 values of the non-negative posit codes [0, 2^(N-1)).

    Strictly increasing (posits order like two's-complement integers), with
    table[0] == 0. Computed once per (N, ES).
    """
    _check_config(N, ES)
    codes = np.arange(1 << (N - 1), dtype=np.int64)
    vals = posit_decode_np(codes, N, ES)
    vals[0] = 0.0
    assert np.all(np.diff(vals) > 0), "posit value table must be monotonic"
    return vals


def posit_max(N: int, ES: int) -> float:
    return float(posit_value_table(N, ES)[-1])


def posit_min_pos(N: int, ES: int) -> float:
    return float(posit_value_table(N, ES)[1])


def _encode_impl(x, N: int, ES: int, xp, table, allow_zero: bool):
    a = xp.abs(x)
    L = 1 << (N - 1)
    if xp is np:
        idx = np.searchsorted(table, a)
        take = lambda i: table[i]
    else:
        method = "compare_all" if L <= _SELECT_MAX else "scan"
        idx = jnp.searchsorted(jnp.asarray(table), a, method=method)
        take = lambda i: _table_lookup(table, i)
    idx = xp.clip(idx, 0, L - 1)
    lo = xp.clip(idx - 1, 0, L - 1)
    hi = idx
    dlo = a - take(lo)
    dhi = take(hi) - a
    # Nearest; ties -> even code (one of two consecutive codes is even).
    take_lo = (dlo < dhi) | ((dlo == dhi) & (lo % 2 == 0))
    code = xp.where(take_lo, lo, hi).astype(xp.int32)
    if not allow_zero:
        # Posit standard: nonzero values never round to zero (minpos floor).
        code = xp.where((a > 0) & (code == 0), 1, code)
    # Saturate above maxpos (searchsorted already clamped to L-1).
    neg = x < 0
    code = xp.where(neg, (-code) & ((1 << N) - 1), code)
    code = xp.where(a == 0, 0, code)
    code = xp.where(xp.isnan(x), NAR(N), code)
    return code


def posit_encode_np(x, N: int, ES: int, allow_zero: bool = True) -> np.ndarray:
    """Round float64 values to nearest posit code (ties to even code)."""
    _check_config(N, ES)
    table = posit_value_table(N, ES)
    return _encode_impl(np.asarray(x, dtype=np.float64), N, ES, np, table, allow_zero)


def posit_encode(x, N: int, ES: int, allow_zero: bool = True) -> jax.Array:
    """jnp encode; table is closed over as a constant (2^(N-1) floats)."""
    _check_config(N, ES)
    table = posit_value_table(N, ES).astype(np.float32)
    return _encode_impl(jnp.asarray(x, dtype=jnp.float32), N, ES, jnp, table, allow_zero)


def posit_encode_arith(x, N: int, ES: int) -> jax.Array:
    """Gather-free posit encode: pure lane-wise bit arithmetic (softposit
    style round-to-nearest-even in code space).

    This is the TPU-native encoder: no table lookups (the searchsorted
    encoder's gathers do not partition under manual-axis shard_map — XLA
    PartitionGather aborts), just float32 bit dissection + integer RNE.
    Used by the gradient-compression transport; agrees with the canonical
    table encoder to <= 1 ulp of the code lattice (ties at regime
    boundaries may legally differ — bit-level RNE vs real-nearest).
    """
    _check_config(N, ES)
    xf = jnp.asarray(x, jnp.float32)
    bits = jax.lax.bitcast_convert_type(xf, jnp.int32)
    a_bits = bits & 0x7FFFFFFF
    a = jax.lax.bitcast_convert_type(a_bits, jnp.float32)
    e = ((a_bits >> 23) & 0xFF) - 127                    # floor(log2 a)
    frac23 = a_bits & 0x7FFFFF
    k = e >> ES                                          # floor division
    exp_f = e - (k << ES)                                # in [0, 2^ES)
    k_c = jnp.clip(k, -(N - 2), N - 2)
    # regime: k >= 0 -> (k+1) ones then 0 (len k+2); k < 0 -> (-k-1) zeros
    # then 1 (len -k+1)
    r_len = jnp.where(k_c >= 0, k_c + 2, 1 - k_c)
    regime = jnp.where(k_c >= 0, (2 << jnp.clip(k_c + 1, 0, 30)) - 2, 1)
    w = jnp.clip(N - 1 - r_len, 0, N - 1)                # tail bits kept
    tail = (exp_f << 23) | frac23                        # ES+23 bits
    shift_r = jnp.clip(ES + 23 - w, 0, 31)
    body = (regime << w) | (tail >> shift_r)
    # RNE on the dropped bits; integer carry IS correct posit rounding
    # (codes are ordered), including carries into the regime.
    rbit = jnp.where(shift_r > 0, (tail >> jnp.clip(shift_r - 1, 0, 31)) & 1, 0)
    sticky = jnp.where(
        shift_r > 1, (tail & ((1 << jnp.clip(shift_r - 1, 0, 31)) - 1)) != 0,
        False)
    lsb = body & 1
    body = body + (rbit & (sticky | (lsb == 1)).astype(jnp.int32))
    maxpos_code = (1 << (N - 1)) - 1
    body = jnp.clip(body, 0, maxpos_code)
    # sub-minpos handling: nearest of {0, minpos} (allow_zero semantics)
    minpos = float(posit_min_pos(N, ES))
    body = jnp.where(a < minpos / 2, 0, jnp.where(a < minpos,
                                                  jnp.maximum(body, 1), body))
    # super-maxpos saturates
    maxpos = float(posit_max(N, ES))
    body = jnp.where(a >= maxpos, maxpos_code, body)
    neg = bits < 0
    code = jnp.where(neg, (-body) & ((1 << N) - 1), body)
    code = jnp.where(a == 0, 0, code)
    code = jnp.where(jnp.isnan(xf), NAR(N), code)
    return code.astype(jnp.int32)
