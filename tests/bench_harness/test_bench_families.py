"""CPU tests of how a configuration file becomes the model that is served
and counted: the key table of ``bench/cell.py`` (every family the engine
serves, unknown keys refused, tp against the cell's chips), the dense path
pinned to what it built before the table, and the work counts of expert,
windowed and state-space layers worked by hand.

    PYTHONPATH=src python -m pytest -q tests/bench_harness
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.cell import load_cell, model_config, model_fields
from bench.work import kv_flash_decode, model, pofx_matmul

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "configs"


def _config(name: str) -> dict:
    path = ROOT / "bench/configs" / f"{name}.json"
    if not path.exists():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


# -- the dense path, as it was built before the key table --------------------

def _dense_model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        act=config["hidden_act"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]))


# name: (sum of k.n, token_flops at 0 and 1024, pofx_matmul at 24 rows)
DENSE = {
    "yi9b": (8566865920, 17133731840.0, 17939038208.0,
             (411209564160.0, 8825022464.0)),
    "ds67b-l12": (9143582720, 18287165440.0, 18689818624.0,
                  (438891970560.0, 9279995904.0)),
}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_model_config_is_unchanged(name):
    cfg = _config(name)
    assert model_config(cfg) == _dense_model_config(cfg)


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_work_counts_are_unchanged(name):
    cfg = _config(name)
    weights, f0, f1024, step = DENSE[name]
    assert sum(k * n for k, n in model.decode_matmuls(cfg)) == weights
    assert model.token_flops(cfg, 0) == f0
    assert model.token_flops(cfg, 1024) == f1024
    assert pofx_matmul.decode_step_work(cfg, 24) == step


# -- every family the engine serves --------------------------------------------

FAMILIES = {"tiny-moe": "moe", "tiny-ssm": "ssm", "tiny-hybrid": "hybrid"}


def _cell(config: dict, tp: int = 1):
    from bench.cell import Cell
    cfg = dict(config, serving=dict(config["serving"], tp=tp))
    return Cell(name=f"{cfg['name']}.one", config_name=cfg["name"],
                config=cfg, traffic_name="none", mix={}, chips=tp,
                end_to_end=[], per_layer=[])


def _serve_one(cell, seed: int = 2**31 + 3) -> tuple:
    from bench.harness import build, make_engine
    from repro.launch.engine import Request
    model_, params = build(cell, seed)
    engine = make_engine(model_, params, cell, seed)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cell.config["vocab_size"], 20, dtype=np.int32)
    engine.submit(Request(rid=0, prompt=prompt, max_new=6))
    engine.admit_ready()
    while engine.active_rids:
        engine.step()
    return list(engine._states[0].out), model_


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_file_builds_and_serves_its_family(name):
    cfg = _config(name)
    mc = model_config(cfg)
    assert mc.family == FAMILIES[name]
    out, served = _serve_one(_cell(cfg))
    assert served.cfg == mc
    assert len(out) == 6
    assert all(0 <= t < cfg["vocab_size"] for t in out)


def test_family_keys_reach_their_fields():
    moe = model_config(_config("tiny-moe"))
    assert (moe.n_experts, moe.top_k, moe.n_shared_experts,
            moe.moe_every) == (4, 2, 1, 2)
    ssm = model_config(_config("tiny-ssm"))
    assert (ssm.ssm_state, ssm.d_inner, ssm.conv_width,
            ssm.dt_rank) == (16, 256, 4, 8)
    hyb = model_config(_config("tiny-hybrid"))
    assert (hyb.ssm_state, hyb.d_inner, hyb.ssm_head_dim, hyb.attn_every,
            hyb.ssm_chunk) == (16, 256, 32, 2, 16)
    assert model_config(dict(_config("yi9b"), use_qk_norm=True)).qk_norm


@pytest.mark.parametrize("key, value", [
    ("moe_intermediate_size", 1408),          # no field holds it yet
    ("layer_types", ["full_attention"]),
    ("sliding_window", 4096),                 # only null is served
    ("attention_bias", True),
])
def test_key_the_system_cannot_serve_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        model_fields(dict(_config("yi9b"), **{key: value}))


def test_two_names_for_one_field_must_agree():
    cfg = dict(_config("tiny-moe"), num_local_experts=8)
    with pytest.raises(ValueError, match="n_experts"):
        model_fields(cfg)
    assert model_fields(dict(cfg, num_local_experts=4))["n_experts"] == 4
    with pytest.raises(ValueError, match="d_ff"):
        model_fields(dict(_config("yi9b"), system={"d_ff": 4096}))


def test_published_keys_are_counted_not_served():
    """A key under "published" reaches no ModelConfig field; the work
    count reads it."""
    cfg = _config("yi9b")
    win = dict(cfg, published={"sliding_window": 512})
    assert model_fields(win) == model_fields(cfg)
    assert model.attention_layers(win) == [(48, 512)]
    assert model.token_flops(win, 4096) == model.token_flops(cfg, 512)


def _bench_root(tmp_path, chips: int, tp: int) -> Path:
    cfg = dict(_config("yi9b"))
    cfg["serving"] = dict(cfg["serving"], tp=tp)
    (tmp_path / "yi9b.json").write_text(json.dumps(cfg))
    bench = {"configs": [{"name": "yi9b", "file": "yi9b.json"}],
             "workloads": [{"name": "yi9b.decode_batch", "config": "yi9b",
                            "traffic": "decode_batch", "chips": chips}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_tp_beyond_the_cells_chips_is_refused(tmp_path):
    with pytest.raises(ValueError, match="tp = 2"):
        load_cell("yi9b.decode_batch", _bench_root(tmp_path, 1, 2))
    assert load_cell("yi9b.decode_batch",
                     _bench_root(tmp_path, 4, 2)).chips == 4


_TP_CHILD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax
from test_bench_families import _cell, _config, _serve_one
cfg = _config("tiny-moe")
one, _ = _serve_one(_cell(cfg, tp=1))
two, m = _serve_one(_cell(cfg, tp=2))
print(json.dumps({{"devices": jax.device_count(), "tp": m.tp_size,
                  "one": [int(t) for t in one], "two": [int(t) for t in two]}}))
"""


def test_serving_tp_builds_a_sharded_engine():
    """``serving.tp`` shards the model over that many chips (two CPU
    devices here), and the engine serves through it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = _TP_CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                            tests=str(Path(__file__).resolve().parent))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 2 and got["tp"] == 2
    assert len(got["two"]) == len(got["one"]) == 6


# -- work counts worked by hand -------------------------------------------------

# d 8, H 2, G 1, Dh 4, f 16, V 32, 3 layers: the first dense, two routing to
# 2 of 4 experts of width 6, with one shared expert. The expert width and
# the leading dense layer have no ModelConfig field yet: they sit under
# "published".
TINY_MOE = {
    "name": "hand-moe", "family": "moe", "hidden_size": 8,
    "intermediate_size": 16, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
    "vocab_size": 32, "hidden_act": "silu", "n_routed_experts": 4,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "published": {"moe_intermediate_size": 6, "first_k_dense_replace": 1}}


def test_expert_work_by_hand():
    # attention q 64, k 32, v 32, o 64 = 192; dense MLP 3 x 8 x 16 = 384;
    # expert layer: router 8 x 4 = 32, 2 routed x 3 x 8 x 6 = 288, shared
    # 3 x 8 x 6 = 144; head 8 x 32 = 256
    assert model.n_expert_layers(TINY_MOE) == (1, 2)
    weights = (192 + 384) + 2 * (192 + 32 + 288 + 144) + 256
    assert sum(k * n for k, n in model.decode_matmuls(TINY_MOE)) == weights
    assert model.token_flops(TINY_MOE, 0) == 2 * 2144
    assert model.token_flops(TINY_MOE, 5) == 2 * 2144 + 4 * 3 * 2 * 4 * 5


@pytest.mark.parametrize("rows", [1, 3, 24])
def test_expert_kernel_work_by_hand(rows):
    """pofx_matmul runs attention, the dense and shared MLPs and the head;
    the router and the routed experts run elsewhere. Its (k, n) per step:
    attention (8, 8), (8, 4), (8, 4), (8, 8) in 3 layers; MLP (8, 16),
    (8, 16), (16, 8) in the dense layer; shared (8, 6), (8, 6), (6, 8) in
    the 2 expert layers; head (8, 32)."""
    shapes = ([(8, 8), (8, 4), (8, 4), (8, 8)] * 3
              + [(8, 16), (8, 16), (16, 8)]
              + [(8, 6), (8, 6), (6, 8)] * 2 + [(8, 32)])
    R = rows
    flops = sum(2 * R * k * n for k, n in shapes)
    nbytes = sum(k * n + 2 * R * k + 4 * n + 4 * R * n for k, n in shapes)
    assert flops == 2 * R * (192 * 3 + 384 + 144 * 2 + 256)
    assert pofx_matmul.decode_step_work(TINY_MOE, R) == (flops, nbytes)


# two layers of H 2, Dh 4: the first windowed to 4 positions, the second
# full. The window has no ModelConfig field yet.
TINY_WINDOW = {
    "name": "hand-window", "hidden_size": 8, "intermediate_size": 16,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
    "num_hidden_layers": 2, "vocab_size": 32, "hidden_act": "silu",
    "published": {"sliding_window": 4,
                  "layer_types": ["sliding_attention", "full_attention"]}}


@pytest.mark.parametrize("context, attended", [(3, 3 + 3), (10, 4 + 10)])
def test_windowed_work_by_hand(context, attended):
    weights = 2 * (192 + 384) + 256
    assert model.token_flops(TINY_WINDOW, context) == \
        2 * weights + 4 * 2 * 4 * attended
    f, _ = kv_flash_decode.decode_step_work(TINY_WINDOW, [context])
    assert f == 4 * 2 * 4 * attended


# mamba1: d 8, inner 16, state 4, time-step rank 2, 2 layers, vocab 32
TINY_MAMBA1 = {
    "name": "hand-mamba1", "family": "ssm", "hidden_size": 8,
    "intermediate_size": 16, "state_size": 4, "time_step_rank": 2,
    "conv_kernel": 4, "num_hidden_layers": 2, "vocab_size": 32}
# mamba2: d 8, inner 2 x 8, state 4, head 4 (4 heads), 3 layers, and a
# shared attention block (H 2, G 2, Dh 4, gated MLP 16) before every 2
TINY_MAMBA2 = {
    "name": "hand-mamba2", "family": "hybrid", "hidden_size": 8,
    "intermediate_size": 16, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
    "vocab_size": 32, "hidden_act": "silu", "mamba_d_state": 4,
    "mamba_expand": 2, "mamba_headdim": 4, "system": {"attn_every": 2}}


def test_state_space_work_by_hand():
    # in 8 x 32, x 16 x (2 + 8), dt 2 x 16, out 16 x 8 a layer; head 256
    w1 = 2 * (256 + 160 + 32 + 128) + 256
    assert sum(k * n for k, n in model.decode_matmuls(TINY_MAMBA1)) == w1
    scan1 = 4 * 2 * 16 * 4                  # update and read-out
    for ctx in (0, 100):
        assert model.token_flops(TINY_MAMBA1, ctx) == 2 * w1 + scan1
    # pofx_matmul runs in and out only (x and dt run elsewhere)
    assert pofx_matmul.decode_step_work(TINY_MAMBA1, 1)[0] == \
        2 * (2 * (256 + 128) + 256)
    # in 8 x (2 x 16 + 2 x 4 + 4), out 16 x 8 a layer; the shared block
    # (q, k, v, o 4 x 64, MLP 3 x 128) twice; head 256
    w2 = 3 * (8 * 44 + 128) + 2 * (256 + 384) + 256
    assert sum(k * n for k, n in model.decode_matmuls(TINY_MAMBA2)) == w2
    assert model.token_flops(TINY_MAMBA2, 7) == \
        2 * w2 + 2 * 4 * 2 * 4 * 7 + 4 * 3 * 16 * 4
    # every mamba2 and shared-block matmul runs in pofx_matmul
    assert pofx_matmul.decode_step_work(TINY_MAMBA2, 1)[0] == 2 * w2
