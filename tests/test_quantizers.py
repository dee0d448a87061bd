"""QuantSpec/QuantizedTensor tests: pytree behavior, composite paths,
storage accounting, FxP view for the int8 MAC path, error ordering
(the paper's headline Fig. 1 claim)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    QuantSpec,
    dequantize,
    fxp_view,
    fxp_quantize_np,
    fxp_dequantize_np,
    quantize,
    storage_bits,
)
from repro.core.analysis import weight_error
from proptest import Floats, given


def _weights(shape=(128, 64), scale=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal(shape) * scale).astype(np.float32))


@pytest.mark.parametrize("spec", [
    QuantSpec(kind="fxp", M=8, F=7),
    QuantSpec(kind="posit", N=8, ES=2),
    QuantSpec(kind="pofx", N=8, ES=2, path="via_fxp"),
    QuantSpec(kind="pofx", N=8, ES=2, path="direct"),
    QuantSpec(kind="bf16"),
    QuantSpec(kind="fp32"),
])
def test_quantize_dequantize_bounded_error(spec):
    w = _weights()
    qt = quantize(w, spec, axis=-1)
    wq = dequantize(qt, jnp.float32)
    assert wq.shape == w.shape
    assert not bool(jnp.any(jnp.isnan(wq)))
    err = float(jnp.mean(jnp.abs(wq - w)))
    assert err < 5e-3, (spec, err)


@pytest.mark.parametrize("spec", [
    QuantSpec(kind="fxp", M=8, F=7),
    QuantSpec(kind="posit", N=8, ES=2),
    QuantSpec(kind="pofx", N=8, ES=2, path="via_fxp"),
    QuantSpec(kind="pofx", N=8, ES=2, path="direct"),
])
def test_large_leaf_encodes_by_row_blocks_identically(spec, monkeypatch):
    """A leaf above the block size encodes a block of rows at a time (the
    last block short) and gives the codes a whole-array encode gives."""
    from repro.core import quantizers
    w = _weights((1100, 4096)).astype(jnp.bfloat16)
    assert w.size > quantizers._ENCODE_BLOCK > 4096 * 1000
    blocked = quantize(w, spec, axis=-1)
    monkeypatch.setattr(quantizers, "_ENCODE_BLOCK", w.size)
    whole = quantize(w, spec, axis=-1)
    assert blocked.codes.dtype == whole.codes.dtype
    np.testing.assert_array_equal(np.asarray(blocked.codes),
                                  np.asarray(whole.codes))
    np.testing.assert_array_equal(np.asarray(blocked.scale),
                                  np.asarray(whole.scale))


def test_quantized_tensor_is_pytree():
    w = _weights((16, 8))
    qt = quantize(w, QuantSpec(kind="pofx", N=8, ES=2), axis=-1)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    assert len(leaves) == 2  # codes + scale
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert np.array_equal(np.asarray(qt2.codes), np.asarray(qt.codes))
    # flows through jit
    f = jax.jit(lambda q: dequantize(q, jnp.float32).sum())
    assert np.isfinite(float(f(qt)))


def test_paper_fig1_error_ordering():
    """Posit(8,2) beats FxP8 on clustered-near-zero weights (Fig. 1: 0.052
    vs 0.295 average absolute relative error). We check the ordering and a
    >3x gap on a matched distribution (zero-mean, sigma=0.05, range +-0.3),
    using the paper's 'no normalizer' assumption (scale_mode='none')."""
    rng = np.random.default_rng(42)
    w = jnp.asarray(np.clip(rng.standard_normal(20000) * 0.05, -0.3, 0.3).astype(np.float32))
    e_fxp = weight_error(w, QuantSpec(kind="fxp", M=8, F=7, scale_mode="none"))
    e_pos = weight_error(w, QuantSpec(kind="posit", N=8, ES=2, scale_mode="none"))
    assert e_pos["avg_rel"] * 3 < e_fxp["avg_rel"], (e_pos, e_fxp)


def test_storage_bits_accounting():
    w = _weights((100, 10))
    bits = {
        "fp32": 32, "bf16": 16,
    }
    for kind, expect in bits.items():
        qt = quantize(w, QuantSpec(kind=kind))
        assert storage_bits(qt) == 1000 * expect
    # pofx stores N-1 bits/code + fp32 scales (per output channel = 10)
    qt = quantize(w, QuantSpec(kind="pofx", N=8, ES=2, scale_mode="channel_pow2"), axis=-1)
    assert storage_bits(qt) == 1000 * 7 + 10 * 32
    # paper claim: vs FxP8 the code storage is (8-7)/8 = 12.5% smaller;
    # vs FP32 it is 78% smaller
    qt8 = quantize(w, QuantSpec(kind="fxp", M=8, F=7, scale_mode="channel_pow2"), axis=-1)
    assert (storage_bits(qt8) - storage_bits(qt)) / storage_bits(qt8) == pytest.approx(0.125, abs=0.01)


def test_fxp_view_int8_path():
    """The int8 MXU view must reproduce dequantize() exactly."""
    w = _weights((32, 16))
    for spec in [QuantSpec(kind="fxp", M=8, F=7), QuantSpec(kind="pofx", N=8, ES=2)]:
        qt = quantize(w, spec, axis=-1)
        codes, rescale = fxp_view(qt)
        assert codes.dtype == jnp.int8
        recon = codes.astype(jnp.float32) * rescale
        ref = dequantize(qt, jnp.float32)
        np.testing.assert_allclose(np.asarray(recon), np.asarray(ref), rtol=0, atol=0)


def test_table5_path_ordering_mechanism():
    """FxP->Posit->FxP must round-trip FxP-representable weights much better
    than the direct Posit->FxP path (truncation bias) — the mechanism behind
    Table 5's accuracy collapse of Posit_FxP."""
    rng = np.random.default_rng(3)
    w_f = fxp_dequantize_np(fxp_quantize_np(rng.standard_normal(8192) * 0.2, 8, 7), 7)
    w = jnp.asarray(w_f.astype(np.float32))
    direct = quantize(w, QuantSpec(kind="pofx", N=8, ES=2, path="direct", scale_mode="none"))
    via = quantize(w, QuantSpec(kind="pofx", N=8, ES=2, path="via_fxp", scale_mode="none"))
    e_direct = float(jnp.mean(jnp.abs(dequantize(direct, jnp.float32) - w)))
    e_via = float(jnp.mean(jnp.abs(dequantize(via, jnp.float32) - w)))
    assert e_via <= e_direct


@given(seed=11, examples=25, x=Floats(lo=-4, hi=4, shape=(512,)))
def test_property_dequantize_within_lattice_gap(x):
    """Property: pofx dequantized values never exceed the normalizer range
    and error is bounded by the local lattice gap + truncation ulp."""
    w = jnp.asarray(x.astype(np.float32))
    spec = QuantSpec(kind="pofx", N=8, ES=2, scale_mode="tensor_pow2")
    qt = quantize(w, spec)
    wq = np.asarray(dequantize(qt, jnp.float32))
    scale = float(np.asarray(qt.scale).reshape(-1)[0])
    assert np.all(np.abs(wq) <= scale)
    # error bounded by (coarsest normalized gap + fxp ulp) * scale
    gap = (0.25 + 2 ** -7) * scale
    assert np.all(np.abs(wq - x) <= gap + 1e-6)
