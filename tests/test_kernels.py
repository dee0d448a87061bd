"""Pallas kernel tests: shape/dtype sweeps against the ref.py oracles
(interpret mode on CPU), per the deliverable-(c) contract."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import QuantSpec, quantize
from repro.core.quantizers import kv_quantize
from repro.kernels import (default_blocks, fxp_matmul, kv_flash_decode,
                           pofx_decode, pofx_matmul, quant_matmul)
from repro.kernels.ref import (decode_norm_to_fxp, fxp_matmul_ref,
                               kv_flash_decode_ref, pofx_decode_ref,
                               pofx_matmul_ref)
from proptest import Floats, given

RNG = np.random.default_rng(1234)

DECODE_SHAPES = [(8, 8), (100, 100), (256, 512), (33, 257), (1, 128), (512, 64)]
POSIT_CONFIGS = [(8, 2), (8, 0), (6, 1), (7, 3), (5, 0), (9, 2)]
MM_SHAPES = [(16, 32, 24), (64, 200, 300), (128, 128, 128), (7, 65, 130), (1, 256, 16)]
KV_SPECS = [QuantSpec(kind="fxp", M=8, F=7), QuantSpec(kind="fxp", M=8, F=4),
            QuantSpec(kind="pofx", N=8, ES=2), QuantSpec(kind="pofx", N=6, ES=1)]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("N,ES", POSIT_CONFIGS[:3])
def test_pofx_decode_kernel_exact(shape, N, ES):
    codes = jnp.asarray(RNG.integers(0, 1 << (N - 1), size=shape), dtype=jnp.uint8)
    out = pofx_decode(codes, N, ES, 8, block=(64, 128))
    ref = pofx_decode_ref(codes, N, ES, 8)
    assert out.dtype == jnp.int8
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("N,ES", POSIT_CONFIGS)
def test_pofx_decode_kernel_all_codes(N, ES):
    """Every code value flows through the kernel identically to Algorithm 1."""
    all_codes = np.arange(1 << (N - 1), dtype=np.uint8)
    tile = np.tile(all_codes, (8, 2))  # 2D for BlockSpec
    out = pofx_decode(jnp.asarray(tile), N, ES, 8, block=(8, 64))
    ref = pofx_decode_ref(jnp.asarray(tile), N, ES, 8)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("mode", ["bitlevel", "table"])
def test_pofx_matmul_kernel(m, k, n, mode):
    x = jnp.asarray(RNG.standard_normal((m, k)).astype(np.float32))
    codes = jnp.asarray(RNG.integers(0, 128, size=(k, n)), dtype=jnp.uint8)
    scale = jnp.asarray((np.abs(RNG.standard_normal(n)) + 0.1).astype(np.float32))
    y = pofx_matmul(x, codes, scale, 8, 2, 8, blocks=(32, 128, 64), decode_mode=mode)
    ref = pofx_matmul_ref(x, codes, scale, 8, 2, 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("N,ES", POSIT_CONFIGS)
@pytest.mark.parametrize("mode", ["bitlevel", "table"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pofx_matmul_every_code_exact(N, ES, mode, dtype):
    """identity @ codes returns each code's decoded value bit for bit, for
    every code, under both decode modes and both MXU operand dtypes ((9, 2)
    has 256 codes, more than the table's 128 lanes, and takes bitlevel)."""
    M = 8
    all_codes = np.resize(np.arange(1 << (N - 1)), (128, 384))
    codes = jnp.asarray(all_codes, jnp.uint8)
    scale = jnp.asarray(np.exp2(RNG.integers(-3, 4, 384)), jnp.float32)
    y = pofx_matmul(jnp.eye(128, dtype=dtype), codes, scale, N, ES, M,
                    blocks=(64, 256, 128), decode_mode=mode)
    fxp = decode_norm_to_fxp(jnp.asarray(all_codes, jnp.int32), N, ES, M)
    want = fxp.astype(jnp.float32) / (1 << (M - 1)) * scale
    assert np.array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("M", [8, 12])
def test_pofx_matmul_bf16_x_exact_products(M):
    """A bf16 x meets a bf16 weight tile only where every FxP(M, M-1) value
    is exact in bf16 (M <= 8); wider M keeps f32. Either way every product
    equals the f32 oracle's, so the result differs only by accumulation
    order: within 1e-6 of the sum of the products' magnitudes."""
    m, k, n = 24, 320, 256
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.bfloat16)
    codes = jnp.asarray(RNG.integers(0, 128, size=(k, n)), jnp.uint8)
    scale = jnp.asarray((np.abs(RNG.standard_normal(n)) + 0.1).astype(np.float32))
    y = pofx_matmul(x, codes, scale, 8, 2, M, blocks=(32, 128, 64))
    xf = x.astype(jnp.float32)
    ref = np.asarray(pofx_matmul_ref(xf, codes, scale, 8, 2, M))
    w = decode_norm_to_fxp(codes.astype(jnp.int32), 8, 2, M) / (1 << (M - 1))
    mag = np.asarray(jnp.abs(xf) @ jnp.abs(w) * scale)
    assert np.all(np.abs(np.asarray(y) - ref) <= 1e-6 * mag)


@pytest.mark.parametrize("N,ES", POSIT_CONFIGS[:5])
def test_pofx_matmul_table_code_zero_is_zero(N, ES):
    """The table's entry for code 0 is 0, as the zero padding of ragged
    K and N tiles assumes: an all-zero code matrix gives an exact zero."""
    x = jnp.asarray(RNG.standard_normal((5, 70)), jnp.float32)
    codes = jnp.zeros((70, 130), jnp.uint8)
    y = pofx_matmul(x, codes, jnp.ones((130,), jnp.float32), N, ES, 8,
                    blocks=(32, 128, 64), decode_mode="table")
    assert np.array_equal(np.asarray(y), np.zeros((5, 130), np.float32))


def test_pofx_matmul_rejects_unknown_decode_mode():
    x = jnp.ones((8, 64), jnp.float32)
    codes = jnp.zeros((64, 128), jnp.uint8)
    with pytest.raises(ValueError, match="decode_mode"):
        pofx_matmul(x, codes, jnp.ones((128,)), 8, 2, 8, decode_mode="onehot")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pofx_matmul_activation_dtypes(dtype):
    x = jnp.asarray(RNG.standard_normal((32, 64)).astype(np.float32)).astype(dtype)
    codes = jnp.asarray(RNG.integers(0, 128, size=(64, 48)), dtype=jnp.uint8)
    scale = jnp.ones((48,), jnp.float32)
    y = pofx_matmul(x, codes, scale, 8, 2, 8, blocks=(32, 48, 64))
    ref = pofx_matmul_ref(x, codes, scale, 8, 2, 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("N,ES", POSIT_CONFIGS)
@pytest.mark.parametrize("M", [4, 8, 12, 16])
def test_pad_code_zero_decodes_to_zero(N, ES, M):
    """Regression for the matmul kernels' zero padding: ``pofx_matmul``
    pads code tiles with 0 on the claim that code 0 decodes to value 0 (so
    padded K-dim tiles contribute nothing to the accumulator), and
    ``kv_flash_decode`` zero-pads ragged S tiles the same way. A LUT or
    bit-level decode change that broke this would silently corrupt every
    padded tile — pin it across the supported (N, ES, M) grid."""
    zero = jnp.zeros((1, 1), jnp.int32)
    assert int(decode_norm_to_fxp(zero, N, ES, M)[0, 0]) == 0


def test_default_blocks_table():
    # every backend entry is a 3-tuple; the active backend resolves
    for backend in ("tpu", "cpu", "gpu"):
        assert len(default_blocks(backend)) == 3
    assert default_blocks("unknown-backend") == default_blocks("tpu")
    assert len(default_blocks()) == 3


@pytest.mark.parametrize("spec", KV_SPECS, ids=lambda s: f"{s.kind}{s.N if s.kind=='pofx' else s.M}")
@pytest.mark.parametrize("block_s", [8, 16, 64])
def test_kv_flash_decode_matches_ref(spec, block_s):
    """Fused kernel vs the XLA dequantize-on-read oracle, ragged per-slot
    positions included (masked tail + zero-padded tiles)."""
    rng = np.random.default_rng(11)
    B, G, R, Dh, S = 3, 2, 4, 32, 40
    q = jnp.asarray(rng.standard_normal((B, G, R, Dh)), jnp.float32)
    ks = jnp.asarray(np.exp2(rng.integers(-1, 3, (B, G, 1, Dh))), jnp.float32)
    vs = jnp.asarray(np.exp2(rng.integers(-2, 2, (B, G, 1, Dh))), jnp.float32)
    kc = kv_quantize(jnp.asarray(rng.standard_normal((B, G, S, Dh)),
                                 jnp.float32), spec, ks)
    vc = kv_quantize(jnp.asarray(rng.standard_normal((B, G, S, Dh)),
                                 jnp.float32), spec, vs)
    pos = jnp.asarray([1, 17, 40], jnp.int32)   # ragged, incl. full cache
    out = kv_flash_decode(q, kc, ks, vc, vs, pos, spec, block_s=block_s)
    ref = kv_flash_decode_ref(q, kc, ks, vc, vs, pos, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kv_flash_decode_scalar_pos_and_shape_guards():
    spec = QuantSpec(kind="fxp", M=8, F=7)
    rng = np.random.default_rng(5)
    B, G, R, Dh, S = 2, 1, 2, 16, 12
    q = jnp.asarray(rng.standard_normal((B, G, R, Dh)), jnp.float32)
    ones = jnp.ones((B, G, 1, Dh), jnp.float32)
    kc = kv_quantize(jnp.asarray(rng.standard_normal((B, G, S, Dh)),
                                 jnp.float32), spec, ones)
    vc = kv_quantize(jnp.asarray(rng.standard_normal((B, G, S, Dh)),
                                 jnp.float32), spec, ones)
    out = kv_flash_decode(q, kc, ones, vc, ones, jnp.asarray(7), spec,
                          block_s=4)
    ref = kv_flash_decode_ref(q, kc, ones, vc, ones, jnp.asarray(7), spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="k_scale"):
        kv_flash_decode(q, kc, jnp.ones((B, G, S, Dh)), vc, ones,
                        jnp.asarray(7), spec)
    with pytest.raises(ValueError, match="v_scale"):
        kv_flash_decode(q, kc, ones, vc, jnp.ones((B, G, S, Dh)),
                        jnp.asarray(7), spec)
    with pytest.raises(ValueError, match="mismatch"):
        kv_flash_decode(q, kc, ones, vc[:, :, :-1], ones, jnp.asarray(7),
                        spec)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
def test_fxp_matmul_kernel_exact(m, k, n):
    a = jnp.asarray(RNG.integers(-127, 128, size=(m, k)), dtype=jnp.int8)
    b = jnp.asarray(RNG.integers(-127, 128, size=(k, n)), dtype=jnp.int8)
    out = fxp_matmul(a, b, blocks=(32, 64, 32))
    assert out.dtype == jnp.int32
    assert np.array_equal(np.asarray(out), np.asarray(fxp_matmul_ref(a, b)))


def test_fxp_matmul_accumulator_headroom():
    """Worst-case accumulation must not overflow int32 (3M-bit argument)."""
    k = 4096  # 127*127*4096 ~ 2^26*4096/64 ... = 6.6e7 << 2^31
    a = jnp.full((8, k), 127, jnp.int8)
    b = jnp.full((k, 8), 127, jnp.int8)
    out = fxp_matmul(a, b, blocks=(8, 8, 512))
    assert int(out[0, 0]) == 127 * 127 * k


@given(seed=5, examples=10, x=Floats(lo=-2, hi=2, shape=(16, 96)))
def test_property_quant_matmul_close_to_float(x):
    """Property: pofx kernel matmul approximates the float matmul with error
    bounded by the quantization error times activation norm."""
    w = (np.random.default_rng(0).standard_normal((96, 32)) * 0.1).astype(np.float32)
    xq = jnp.asarray(x.astype(np.float32))
    qt = quantize(jnp.asarray(w), QuantSpec(kind="pofx", N=8, ES=2), axis=-1)
    y_kernel = quant_matmul(xq, qt, use_kernel=True)
    y_float = xq @ w
    denom = np.maximum(np.abs(np.asarray(y_float)), 1.0)
    rel = np.abs(np.asarray(y_kernel) - np.asarray(y_float)) / denom
    assert rel.mean() < 0.05


def test_quant_matmul_kernel_equals_xla_path():
    x = jnp.asarray(RNG.standard_normal((10, 64)).astype(np.float32))
    w = jnp.asarray((RNG.standard_normal((64, 80)) * 0.05).astype(np.float32))
    qt = quantize(w, QuantSpec(kind="pofx", N=8, ES=2), axis=-1)
    yk = quant_matmul(x, qt, use_kernel=True)
    yx = quant_matmul(x, qt, use_kernel=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yx), rtol=1e-4, atol=1e-4)


def test_quant_matmul_batched_leading_dims():
    x = jnp.asarray(RNG.standard_normal((2, 3, 64)).astype(np.float32))
    w = jnp.asarray((RNG.standard_normal((64, 32)) * 0.1).astype(np.float32))
    qt = quantize(w, QuantSpec(kind="pofx", N=8, ES=2), axis=-1)
    y = quant_matmul(x, qt, use_kernel=True)
    assert y.shape == (2, 3, 32)
