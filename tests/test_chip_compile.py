"""The serving path compiled for a TPU v5e that is described, not attached.

Interpret mode on CPU cannot show what the TPU compiler refuses: a block
that breaks the (8, 128) tiling rule, an int32 operand to the MXU, a program
that does not fit 16 GB. These tests hand the real compiler yi-9b's
published widths with the kernels' TPU settings spelled out (``interpret=
False``, ``DEFAULT_BLOCKS["tpu"]``, ``block_s=512``), since under
``JAX_PLATFORMS=cpu`` the kernels' own defaults pick the CPU branch. The
topology is described inside a fixture, never at import time: only one
process may load the TPU library, and a test worker that loads it while
collecting would take it from the others.
"""
import functools

import numpy as np
import pytest

D_MODEL, D_FF, HEADS, KV_HEADS, D_HEAD = 4096, 11008, 32, 4, 128
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile()


def _kernel_text(name, fn, *args) -> str:
    """The compiled program's text; its kernel call carries the kernel's
    own name (``pallas_call(name=...)``), which a profile shows."""
    txt = _compile(fn, *args).as_text()
    calls = [line for line in txt.splitlines() if "tpu_custom_call" in line]
    assert calls
    assert all(f"/{name}/pallas_call" in line for line in calls), calls[0]
    return txt


def _kv_spec(kind):
    from repro.core.quantizers import QuantSpec
    return (QuantSpec(kind="fxp", M=8, F=7) if kind == "fxp8"
            else QuantSpec(kind="pofx", N=8, ES=2, M=8))


@pytest.mark.parametrize("m", [8, 512])
def test_pofx_matmul_compiles(one_chip, m):
    import jax
    import jax.numpy as jnp
    from repro.kernels import DEFAULT_BLOCKS
    from repro.kernels.pofx_matmul import pofx_matmul
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _kernel_text(
        "pofx_matmul",
        lambda x, c, s: pofx_matmul(x, c, s, 8, 2, 8,
                                    blocks=DEFAULT_BLOCKS["tpu"],
                                    interpret=False),
        S((m, D_FF), jnp.bfloat16), S((D_FF, D_MODEL), jnp.uint8),
        S((D_MODEL,), jnp.float32))


def test_fxp_matmul_compiles(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels import DEFAULT_BLOCKS
    from repro.kernels.fxp_matmul import fxp_matmul
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _kernel_text(
        "fxp_matmul",
        lambda a, b: fxp_matmul(a, b, blocks=DEFAULT_BLOCKS["tpu"],
                                interpret=False),
        S((512, D_MODEL), jnp.int8), S((D_MODEL, D_FF), jnp.int8))


@pytest.mark.parametrize("kind", ["fxp8", "pofx8"])
def test_kv_flash_decode_compiles(one_chip, kind):
    import jax
    import jax.numpy as jnp
    from repro.core.quantizers import kv_code_dtype
    from repro.kernels.kv_flash_decode import kv_flash_decode
    spec = _kv_spec(kind)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    B, G, R, Sl = 4, KV_HEADS, HEADS // KV_HEADS, 2048
    codes = S((B, G, Sl, D_HEAD), kv_code_dtype(spec))
    scale = S((B, G, 1, D_HEAD), jnp.float32)
    _kernel_text(
        "kv_flash_decode",
        lambda q, kc, ks, vc, vs, pos: kv_flash_decode(
            q, kc, ks, vc, vs, pos, spec, block_s=512, interpret=False),
        S((B, G, R, D_HEAD), jnp.float32), codes, scale, codes, scale,
        S((B,), jnp.int32))


def test_kv_flash_paged_decode_compiles(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels.kv_flash_paged_decode import kv_flash_paged_decode
    spec = _kv_spec("fxp8")
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    B, G, R, page, max_pages = 4, KV_HEADS, HEADS // KV_HEADS, 16, 128
    pool = S((B * max_pages + 1, G, page, D_HEAD), jnp.int8)
    scale = S((G, 1, D_HEAD), jnp.float32)
    _kernel_text(
        "kv_flash_paged_decode",
        lambda q, kc, ks, vc, vs, tbl, pos: kv_flash_paged_decode(
            q, kc, ks, vc, vs, tbl, pos, spec, interpret=False),
        S((B, G, R, D_HEAD), jnp.float32), pool, scale, pool, scale,
        S((B, max_pages), jnp.int32), S((B,), jnp.int32))


def test_pofx_decode_compiles(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels.pofx_decode import pofx_decode
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _kernel_text(
        "pofx_decode", lambda c: pofx_decode(c, 8, 2, 8, interpret=False),
        S((D_FF, D_MODEL), jnp.uint8))


@pytest.fixture(scope="module")
def quantized_init(one_chip):
    """yi-9b made and quantized to pofx8, compiled for one v5e."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, RunConfig
    from repro.nn.models import build_model
    model = build_model(ARCHS["yi-9b"], RunConfig(remat="none"))
    return _compile(lambda key: model.init_quantized(key, "pofx8"),
                    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))


def test_full_width_quantized_init_fits_one_chip(quantized_init):
    """The float model (17.7 GB in bf16) never exists, and codes plus
    temporaries fit."""
    mem = quantized_init.memory_analysis()
    assert mem.output_size_in_bytes > 8e9            # the codes are there
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"


def test_full_width_quantized_init_has_no_gather(quantized_init):
    """The encoder searches and reads its small value table with compares
    and selects: a TPU gather of one table entry per weight costs several
    ns each, minutes over 8.8B weights."""
    assert " gather(" not in quantized_init.as_text()


@pytest.mark.parametrize("kind", ["pofx8", "fxp8f7"])
def test_weight_decode_has_no_gather(one_chip, kind):
    """The XLA path's weight decode (codes -> values, and the pofx -> fxp
    code view the fxp kernel reads) at yi-9b's MLP width, gather-free."""
    import jax
    import jax.numpy as jnp
    from repro.core.policy import parse_spec
    from repro.core.quantizers import dequantize, fxp_view, quantize
    spec = parse_spec(kind)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    qt = jax.eval_shape(lambda w: quantize(w, spec, axis=-1),
                        S((D_FF, D_MODEL), jnp.bfloat16))
    qt = jax.tree.map(lambda x: S(x.shape, x.dtype), qt)
    for fn in (lambda q: dequantize(q, jnp.bfloat16), lambda q: fxp_view(q)):
        assert " gather(" not in _compile(fn, qt).as_text()


@pytest.mark.parametrize("arch", ["yi-9b", "moonshot-v1-16b-a3b",
                                  "llama4-maverick-400b-a17b"])
def test_init_quantized_matches_apply_policy(arch):
    """CPU, smoke width: the per-layer init-and-quantize gives the codes
    apply_policy gives the eagerly made float model, bit for bit."""
    import jax
    from repro.configs import ARCHS, RunConfig, smoke
    from repro.nn.models import apply_policy, build_model
    model = build_model(smoke(ARCHS[arch]), RunConfig(remat="none"))
    key = jax.random.PRNGKey(3)
    policy = "attn/*=pofx8es2,mlp/*=fxp8f7,*=bf16"   # codes of both kinds
    want = apply_policy(model.init(key), policy)
    got = model.init_quantized(key, policy)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
