"""Posit codec tests: Table 2 golden values, exhaustive bit-level checks,
jnp==numpy exactness, and property tests (encode/decode invariants)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    NAR,
    norm_compress,
    norm_decode_np,
    norm_encode_np,
    norm_expand,
    norm_max,
    pack_bits,
    posit_decode,
    posit_decode_np,
    posit_encode,
    posit_encode_np,
    posit_max,
    posit_min_pos,
    posit_value_table,
    unpack_bits,
)
from proptest import Floats, given

ALL_CONFIGS = [(N, ES) for N in range(4, 11) for ES in range(0, 4)] + [(16, 2), (16, 3), (12, 1)]


def test_table2_paper_values():
    """Exact reproduction of paper Table 2: Posit(4,0)."""
    vals = posit_decode_np(np.arange(16), 4, 0)
    expect = [0, 0.25, 0.5, 0.75, 1, 1.5, 2, 4,
              np.nan, -4, -2, -1.5, -1, -0.75, -0.5, -0.25]
    for c, (v, e) in enumerate(zip(vals, expect)):
        if np.isnan(e):
            assert np.isnan(v), c
        else:
            assert v == e, (c, v, e)


def test_table2_normalized_mapping():
    """Paper Table 2 highlighted rows: posit <-> ExPAN(N)D code mapping."""
    posit_codes = [0b0000, 0b0001, 0b0010, 0b0011, 0b1100, 0b1101, 0b1110, 0b1111]
    expannd = [0b000, 0b001, 0b010, 0b011, 0b100, 0b101, 0b110, 0b111]
    got = norm_compress(np.array(posit_codes), 4)
    assert list(got) == expannd
    assert list(norm_expand(np.array(expannd), 4)) == posit_codes


@pytest.mark.parametrize("N,ES", ALL_CONFIGS)
def test_decode_monotonic_and_symmetric(N, ES):
    codes = np.arange(1 << N)
    vals = posit_decode_np(codes, N, ES)
    # signed-code ordering == value ordering (posit core property)
    signed = np.where(codes >= (1 << (N - 1)), codes - (1 << N), codes)
    order = np.argsort(signed)
    v = vals[order]
    v = v[~np.isnan(v)]
    assert np.all(np.diff(v) > 0)
    # negation symmetry: decode(-c) == -decode(c)
    pos = codes[1: 1 << (N - 1)]
    neg = (-pos) & ((1 << N) - 1)
    assert np.array_equal(posit_decode_np(neg, N, ES), -vals[pos])


@pytest.mark.parametrize("N,ES", ALL_CONFIGS)
def test_jnp_decode_exact(N, ES):
    c = np.arange(1 << N)
    a = posit_decode_np(c, N, ES)
    b = np.asarray(posit_decode(jnp.asarray(c), N, ES), dtype=np.float64)
    m = ~np.isnan(a)
    assert np.array_equal(a[m], b[m])
    assert np.isnan(b[~m]).all()


@pytest.mark.parametrize("N,ES", [(8, 2), (7, 1), (5, 0), (16, 2), (6, 3)])
def test_encode_roundtrip_identity(N, ES):
    """Every representable posit value encodes back to its own code."""
    c = np.arange(1 << N)
    v = posit_decode_np(c, N, ES)
    m = ~np.isnan(v)
    assert np.array_equal(posit_encode_np(v[m], N, ES), c[m])
    # NaN -> NaR
    assert posit_encode_np(np.array([np.nan]), N, ES)[0] == NAR(N)


@pytest.mark.parametrize("N,ES", [(8, 2), (6, 1)])
def test_encode_jnp_matches_np(N, ES):
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(4096) * np.exp2(rng.integers(-20, 10, 4096))).astype(np.float32)
    a = posit_encode_np(x.astype(np.float64), N, ES)
    b = np.asarray(posit_encode(jnp.asarray(x), N, ES))
    assert np.array_equal(a, b)


@pytest.mark.parametrize("N,ES", [(8, 2), (6, 1), (9, 3)])
def test_encode_selects_match_gathers(N, ES, monkeypatch):
    """Small tables are searched and read with compares and selects (no
    TPU gathers); the codes are those of the gather-based search."""
    from repro.core import posit
    rng = np.random.default_rng(2)
    x = (rng.standard_normal(4096) * np.exp2(rng.integers(-20, 10, 4096))).astype(np.float32)
    x[:3] = [0.0, np.nan, -np.inf]
    selects = np.asarray(posit_encode(jnp.asarray(x), N, ES))
    monkeypatch.setattr(posit, "_SELECT_MAX", 0)
    gathers = np.asarray(posit_encode(jnp.asarray(x), N, ES))
    assert np.array_equal(selects, gathers)


@given(seed=7, examples=30, x=Floats(lo=-1e6, hi=1e6, shape=(256,)))
def test_encode_is_nearest(x):
    """Property: |decode(encode(x)) - x| <= distance to any lattice value."""
    N, ES = 8, 2
    table = posit_value_table(N, ES)
    full = np.concatenate([-table[::-1], table])
    code = posit_encode_np(x, N, ES)
    back = posit_decode_np(code, N, ES)
    err = np.abs(back - x)
    # nearest lattice distance (saturation: clamp to [min, max])
    xc = np.clip(x, full[0], full[-1])
    best = np.min(np.abs(full[None, :] - xc[:, None]), axis=1)
    pad = np.abs(x - xc)  # saturation penalty is unavoidable
    assert np.all(err <= best + pad + 1e-12)


@given(seed=3, examples=30, x=Floats(lo=-8.0, hi=8.0, shape=(128,)))
def test_normalized_encode_saturates(x):
    """Property: normalized codes decode into [-1, norm_max]."""
    N, ES = 8, 1
    code = norm_encode_np(x, N, ES)
    assert np.all(code < (1 << (N - 1)))
    v = norm_decode_np(code, N, ES)
    assert np.all(v >= -1.0) and np.all(v <= norm_max(N, ES)) and norm_max(N, ES) < 1.0
    # in-range values quantize with bounded error (<= one lattice gap)
    inside = (np.abs(x) <= 1.0)
    assert np.all(np.abs(v[inside] - x[inside]) <= 0.26)  # coarsest gap near +/-1 is < 2^-2


@pytest.mark.parametrize("N,ES", [(6, 0), (8, 2), (9, 3)])
def test_normalized_roundtrip_all_codes(N, ES):
    nm = np.arange(1 << (N - 1))
    v = norm_decode_np(nm, N, ES)
    assert np.array_equal(norm_encode_np(v, N, ES), nm)


@pytest.mark.parametrize("k", [3, 5, 7, 8, 11, 15])
def test_bit_packing_roundtrip(k):
    rng = np.random.default_rng(k)
    codes = rng.integers(0, 1 << k, size=999)
    packed = pack_bits(codes, k)
    assert packed.size == int(np.ceil(999 * k / 8))
    assert np.array_equal(unpack_bits(packed, k, 999), codes)


def test_minmax_helpers():
    assert posit_max(8, 2) == posit_decode_np(np.array([127]), 8, 2)[0]
    assert posit_min_pos(8, 2) == posit_decode_np(np.array([1]), 8, 2)[0]
