"""CPU tests of what decides ``correct``: the plain reference against the
system's own weights, whole runs of a small cell through the harness (with
the chip check skipped) that pass when sound and fail with a fault planted
under the timed path, and the float8 control failing the limit.

    PYTHONPATH=src python -m pytest -q tests/bench_harness
"""
import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench.harness import within_limit
from bench.references import dense_gqa as ref

ROOT = Path(__file__).resolve().parents[2]
_LIMITS = [json.loads(p.read_text())["check"]["max_logit_gap"]
           for p in sorted((ROOT / "bench/configs").glob("*.json"))]
# sound runs have to meet the tightest limit; faults and the control have
# to pass the widest
LO, HI = min(_LIMITS), max(_LIMITS)

TINY = {"name": "tiny", "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "tie_word_embeddings": False, "reference": "dense_gqa",
        "check": {"sample": 3, "pad_to": 64, "max_logit_gap": LO}}


def test_pofx_table_is_the_systems_format():
    import jax.numpy as jnp
    from repro.core.quantizers import QuantSpec, dequantize, quantize
    a = np.arange(-128, 128)
    table = ref.pofx_table()
    assert np.array_equal(np.asarray(ref.served_codes(jnp.asarray(a))),
                          table)
    w = np.stack([np.ones(256), a / 128.0]).astype(np.float32)
    qt = quantize(jnp.asarray(w), QuantSpec(kind="pofx", N=8, ES=2, M=8),
                  axis=-1)
    got = np.rint(np.asarray(dequantize(qt, jnp.float32))[1] * 128)
    assert np.array_equal(got.astype(int), table)


def test_reference_weights_are_the_systems():
    """Made independently from the seed, the reference's weights equal the
    values the system decodes from its codes, bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, RunConfig, smoke
    from repro.core.quantizers import dequantize
    from repro.nn.models import build_model
    cfg = dataclasses.replace(smoke(ARCHS["yi-9b"]), n_layers=2)
    params = build_model(cfg, RunConfig(remat="none")).init_quantized(
        jax.random.PRNGKey(2**31 + 5), "pofx8")
    ke, kh, kl = ref._keys(2**31 + 5, 2)
    d, H, G, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ff, V = cfg.d_ff, cfg.padded_vocab
    deq = lambda q: np.asarray(dequantize(q, jnp.float32))
    assert np.array_equal(deq(params["embed"]),
                          ref._served_weight(ke, V, (d,), 1.0))
    assert np.array_equal(deq(params["unembed"]),
                          ref._served_weight(kh, d, (V,), d ** -0.5))
    k1, k2 = jax.random.split(kl[1])
    ka, km = jax.random.split(k1, 6), jax.random.split(k2, 3)
    blk = jax.tree.map(lambda x: x[1], params["blocks"])
    for key, i, o, q in [(ka[1], d, (G, Dh), blk["attn"]["wk"]),
                         (ka[3], H * Dh, (d,), blk["attn"]["wo"]),
                         (km[2], ff, (d,), blk["mlp"]["wo"])]:
        assert np.array_equal(deq(q).reshape(i, -1),
                              ref._served_weight(key, i, o, i ** -0.5))


def _tiny_cell(paged: bool):
    from bench.cell import Cell
    cfg = dict(TINY, serving={
        "weights": "pofx8", "kv_cache": "fxp8", "kernels": True,
        "paged": paged, "page_size": 16, "slots": 4, "max_len": 256,
        "chunk": 4, "prompt_bucket": 32})
    if paged:
        arrivals = {"process": "gamma", "rate_per_s": 4.0, "cv": 2.0,
                    "initial_backlog": 4}
        prompt = {"shared": {"count": 2, "tokens": 32},
                  "unique": {"median": 24, "sigma": 0.5, "min": 8,
                             "max": 60}}
    else:
        arrivals = {"process": "backlog", "per_slot": 32}
        prompt = {"unique": {"median": 24, "sigma": 0.5, "min": 8,
                             "max": 60}}
    mix = {"arrivals": arrivals, "prompt": prompt,
           "output": {"median": 16, "sigma": 0.5, "min": 6, "max": 40},
           "block": 4, "schedule_seed": 7, "lead_in_s": 0.5, "horizon_s": 10}
    e2e = [{"name": "output_tok_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    return Cell(name="tiny.cell", config_name="tiny", config=cfg,
                traffic_name="tiny", mix=mix, chips=1, end_to_end=e2e,
                per_layer=[])


def _run(paged: bool, seed: int = 2**31 + 17) -> dict:
    import jax
    from bench.device import CompileClock
    from bench.harness import run_cell
    from bench.peaks import peaks_for
    return run_cell(_tiny_cell(paged), seed=seed, seconds=2.0, trace=False,
                    devices=jax.devices(), peaks=peaks_for("TPU v5 lite"),
                    clock=CompileClock(), t_process=time.perf_counter(),
                    cache_dir="none")


@pytest.mark.parametrize("paged", [False, True])
def test_sound_run_is_correct(paged):
    res = _run(paged)
    assert res["correct"], res["check"]
    assert res["metrics"]["output_tok_per_s"]["value"] > 0
    assert list(res)[-1] == "check"


def test_altered_token_is_caught(monkeypatch):
    """A token changed where the engine produces it."""
    from repro.launch.engine import ServeEngine
    step = ServeEngine.step

    def altered(self, steps=None):
        done = step(self, steps)
        for st in [self._states[r] for r in self.active_rids] + done:
            st.out[-1] = (st.out[-1] + 1) % TINY["vocab_size"]
        return done

    monkeypatch.setattr(ServeEngine, "step", altered)
    res = _run(False)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > HI


@pytest.mark.parametrize("paged", [False, True])
def test_step_returning_its_state_unchanged_is_caught(monkeypatch, paged):
    """A decode step whose cache comes back as it went in."""
    from repro.nn.models import LM
    decode_step = LM.decode_step

    def stale(self, params, cache, tokens):
        _, logits = decode_step(self, params, cache, tokens)
        return cache, logits

    monkeypatch.setattr(LM, "decode_step", stale)
    res = _run(paged)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > HI


def test_float8_control_fails_the_limit():
    """The reference with float8 activations, at a size the CPU holds,
    puts first tokens whose gap passes the cells' limit."""
    cfg = dict(TINY, hidden_size=512, intermediate_size=1376,
               num_attention_heads=8, num_key_value_heads=2, head_dim=64,
               num_hidden_layers=48, vocab_size=4096)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 4096, 200) for _ in range(2)]
    served = [rng.integers(0, 4096, 100) for _ in range(2)]
    out = ref.logit_gaps(cfg, 3, prompts, served, 64, control=True)
    assert not within_limit(out["control_gap"], HI)
