"""Sharding + dry-run machinery on a small in-process device grid.

The full 512-device dry-run runs via launch/dryrun.py subprocesses (it must
own XLA_FLAGS); here we validate the same machinery — sharding rules,
state/cache sharding trees, lower+compile, HLO cost parser — on an 8-device
grid, plus the posit8 cross-pod gradient path and elastic restore.
"""
import subprocess
import sys
import os

import numpy as np
import pytest

_N_DEV = 8


@pytest.fixture(scope="module")
def jax8():
    os.environ.setdefault("XLA_FLAGS", "")
    import jax
    if jax.device_count() < _N_DEV:
        pytest.skip("needs xla_force_host_platform_device_count (see "
                    "test_dryrun_subprocess)")
    return jax


def test_sharding_rules_divisibility_fallback():
    import jax
    from repro.nn.sharding import make_ctx
    if jax.device_count() != 1:
        pytest.skip("single-device check")
    ctx = make_ctx(None)
    import jax.numpy as jnp
    x = jnp.zeros((4, 6))
    assert ctx.constrain(x, "batch", "mlp") is x  # no mesh: no-op


def test_dryrun_subprocess_small_mesh(tmp_path):
    """End-to-end: lower+compile a smoke arch on 8 fake devices, parse HLO,
    roofline terms present. Mirrors launch/dryrun.py in miniature."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, dataclasses, json
from repro.configs import ARCHS, RunConfig, smoke
from repro.configs.base import ShapeConfig
from repro.nn.models import build_model, input_specs
from repro.launch.train import (make_train_step, abstract_train_state,
                                state_shardings, batch_shardings)
from repro.launch.hlo_parser import analyze_hlo
from repro.launch.hlo_analysis import roofline_terms
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(pod=2, data=2, model=2)
cfg = smoke(ARCHS["yi-9b"])
rcfg = RunConfig(remat="block", sequence_parallel=True, microbatch=2,
                 grad_compression="posit8")
model = build_model(cfg, rcfg, mesh=mesh)
state_abs = abstract_train_state(model)
ss = state_shardings(model, state_abs)
shape = ShapeConfig("t", 64, 8, "train")
batch_abs = input_specs(cfg, shape)
bs = batch_shardings(model, batch_abs)
step = make_train_step(model, mesh)
with mesh:
    compiled = jax.jit(step, in_shardings=(ss, bs), out_shardings=(ss, None),
                       donate_argnums=(0,)).lower(state_abs, batch_abs).compile()
txt = compiled.as_text()
cost = analyze_hlo(txt)
assert cost.flops_per_device > 0
assert cost.wire_bytes_per_device > 0, "expected collectives on 8 devices"
# the posit8 pod transport all-gathers uint8 codes: u8 must appear in a
# collective result type
assert any(k in cost.wire_by_kind for k in ("all-gather", "all-reduce"))
r = roofline_terms(cost.flops_per_device, cost.bytes_per_device,
                   cost.wire_bytes_per_device, 1e9, 8)
assert r["bound"] in ("compute", "memory", "collective")
# ALSO: run the compiled step on real (fake-device) inputs to prove the
# sharded program executes, not just compiles.
import numpy as np
from repro.launch.train import make_train_state
state = jax.device_put(make_train_state(model, jax.random.PRNGKey(0)), ss)
batch = {"tokens": jnp.zeros((8, 64), jnp.int32),
         "labels": jnp.zeros((8, 64), jnp.int32)}
batch = jax.device_put(batch, bs)
new_state, metrics = compiled(state, batch)
assert np.isfinite(float(metrics["loss"]))
print("OK", json.dumps({k: float(v) for k, v in r.items()
                        if isinstance(v, (int, float))}))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_decode_cell_subprocess_small_mesh():
    """Quantized (pofx8) decode step lowers, compiles AND RUNS sharded."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, RunConfig, smoke
from repro.core.quantizers import QuantSpec, QuantizedTensor
from repro.nn.models import build_model, quantize_params
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=4, model=2)
cfg = smoke(ARCHS["deepseek-67b"])
model = build_model(cfg, RunConfig(remat="none"), mesh=mesh)
spec = QuantSpec(kind="pofx", N=8, ES=2, M=8)
params = quantize_params(model.init(jax.random.PRNGKey(0)), spec)
p_shard_plain = model.param_shardings(
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
repl = NamedSharding(mesh, P())
flat_s, td = jax.tree_util.tree_flatten(p_shard_plain, is_leaf=lambda x: x is None)
objs = td.flatten_up_to(params)
p_shard = td.unflatten([QuantizedTensor(s, repl, o.spec)
                        if isinstance(o, QuantizedTensor) else s
                        for s, o in zip(flat_s, objs)])
params = jax.device_put(params, p_shard)
B, S = 8, 64
cache = model.init_cache(B, S)
c_shard = model.cache_shardings(B, S)
cache = jax.device_put(cache, c_shard)
tok = jnp.zeros((B, 1), jnp.int32)
step = jax.jit(model.decode_step, donate_argnums=(1,),
               in_shardings=(p_shard, c_shard, None),
               out_shardings=(c_shard, None))
cache, logits = step(params, cache, tok)
cache, logits = step(params, cache, tok)
assert logits.shape == (B, cfg.padded_vocab)
assert np.all(np.isfinite(np.asarray(logits, np.float32)))
print("OK decode")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK decode" in r.stdout


def test_hlo_parser_on_synthetic_module():
    from repro.launch.hlo_parser import analyze_hlo
    txt = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%ni, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%zero, %x)
  %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body
  %ag = f32[16,8]{1,0} all-gather(%x), replica_groups=[4,2]<=[8], dimensions={0}, channel_id=1
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    c = analyze_hlo(txt)
    assert c.flops_per_device == 5 * 2 * 8 * 8 * 8      # 5 trips x dot
    assert ("body", 5) in c.loops
    # all-gather of 16x8 f32 over group of 2: 512B * 1/2 wire
    assert abs(c.wire_bytes_per_device - 16 * 8 * 4 * 0.5) < 1e-6
