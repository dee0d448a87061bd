"""PoFx (Algorithm 1) tests: exhaustive bit-level equality with the golden
float decode, normalized-variant semantics (unidirectional shift, -1 OF),
LUT consistency, and jnp==numpy parity."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    norm_decode_np,
    pofx_convert,
    pofx_convert_np,
    pofx_lut,
    pofx_norm_lut,
    pofx_normalized,
    pofx_normalized_np,
    posit_decode_np,
)

CONFIGS = [(N, ES) for N in range(4, 11) for ES in range(0, 4)]


def _gold(vals, M, F):
    g = np.trunc(np.nan_to_num(vals) * (1 << F))
    return np.clip(g, -(1 << (M - 1)) + 1, (1 << (M - 1)) - 1)


@pytest.mark.parametrize("N,ES", CONFIGS)
@pytest.mark.parametrize("M,F", [(8, 7), (16, 12), (20, 10), (32, 20)])
def test_pofx_exhaustive_vs_golden(N, ES, M, F):
    codes = np.arange(1 << N)
    vals = posit_decode_np(codes, N, ES)
    out, of = pofx_convert_np(codes, N, ES, M, F)
    assert np.array_equal(out, _gold(vals, M, F))
    # OF flag set exactly when the *truncated* magnitude exceeds the output
    # range (hardware semantics: high bits shifted out, not pre-truncation).
    finite = ~np.isnan(vals)
    overflow = np.trunc(np.abs(np.nan_to_num(vals)) * (1 << F)) > ((1 << (M - 1)) - 1)
    assert np.array_equal(of[finite], overflow[finite])


@pytest.mark.parametrize("N,ES", [(8, 2), (6, 0), (16, 3), (10, 1)])
def test_pofx_jnp_matches_np(N, ES):
    c = np.arange(1 << N)
    o1, f1 = pofx_convert_np(c, N, ES, 16, 14)
    o2, f2 = pofx_convert(jnp.asarray(c), N, ES, 16, 14)
    assert np.array_equal(o1, np.asarray(o2))
    assert np.array_equal(f1, np.asarray(f2))


@pytest.mark.parametrize("N,ES", CONFIGS)
def test_pofx_normalized_exhaustive(N, ES):
    """Normalized variant: F = M-1, truncation, -1 saturates with OF."""
    M = 8
    nm = np.arange(1 << (N - 1))
    out, of = pofx_normalized_np(nm, N, ES, M)
    vals = norm_decode_np(nm, N, ES)
    assert np.array_equal(out, _gold(vals, M, M - 1))
    # -1 is in the normalized lattice but not extractable (paper §4.1.2)
    neg1 = vals == -1.0
    assert np.all(of[neg1])
    assert np.all(out[neg1] == -((1 << (M - 1)) - 1))
    # everything else is in range, no overflow
    assert not np.any(of[~neg1])
    # unidirectional: no output magnitude exceeds 2^(M-1)-1 and all
    # magnitudes strictly below 1.0 in fixed-point
    assert np.all(np.abs(out) <= (1 << (M - 1)) - 1)


@pytest.mark.parametrize("N,ES,M,rounding", [
    (8, 2, 8, "trunc"), (8, 2, 8, "nearest"), (6, 1, 8, "trunc"),
    (9, 3, 12, "trunc"), (10, 0, 16, "nearest")])
def test_pofx_normalized_jnp_matches_np(N, ES, M, rounding):
    """The int32 jnp conversion (the weight decode's and the kernels')
    equals the int64 numpy one, and so the decode table, on every code."""
    nm = np.arange(1 << (N - 1))
    o1, f1 = pofx_normalized_np(nm, N, ES, M, rounding)
    o2, f2 = pofx_normalized(jnp.asarray(nm), N, ES, M, rounding)
    assert np.array_equal(o1, np.asarray(o2))
    assert np.array_equal(f1, np.asarray(f2))


@pytest.mark.parametrize("N,ES", [(8, 2), (7, 1), (6, 0)])
def test_luts_match_bitlevel(N, ES):
    lut = pofx_lut(N, ES, 16, 14)
    out, _ = pofx_convert_np(np.arange(1 << N), N, ES, 16, 14)
    assert np.array_equal(lut, out)
    nlut = pofx_norm_lut(N, ES, 8)
    nout, _ = pofx_normalized_np(np.arange(1 << (N - 1)), N, ES, 8)
    assert np.array_equal(nlut, nout)


def test_truncation_vs_nearest_bias():
    """Stage-D truncation has a systematic negative magnitude bias; the
    beyond-paper 'nearest' knob removes most of it (sanity for Table 5's
    Posit_FxP degradation mechanism)."""
    N, ES, M = 8, 2, 8
    nm = np.arange(1 << (N - 1))
    vals = norm_decode_np(nm, N, ES)
    t, _ = pofx_normalized_np(nm, N, ES, M, rounding="trunc")
    r, _ = pofx_normalized_np(nm, N, ES, M, rounding="nearest")
    err_t = np.abs(t / (1 << (M - 1)) - vals).mean()
    err_r = np.abs(r / (1 << (M - 1)) - vals).mean()
    assert err_r <= err_t
