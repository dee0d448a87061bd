"""Serve-engine throughput under varying request-arrival mixes.

The continuous-batching claim: tokens/s should hold up when requests
arrive staggered (slots refill as others finish) instead of as one
aligned batch — the regime the old one-shot driver could not serve at
all. Three mixes over the same request set:

  burst     — all requests arrive at t=0 (best case for static batching)
  staggered — one request every `gap` decode steps (steady traffic)
  ragged    — burst arrivals but 2x-spread generation lengths (slots
              free at different times; continuous refill does the work)

plus a long-context mix for the quantized KV cache (DESIGN.md §8):

  longctx   — staggered arrivals over long prompts, served three ways:
              bf16 cache, quantized cache via the XLA fallback, quantized
              cache via the fused Pallas flash-decode kernel. Rows record
              the modeled decode KV-cache HBM bytes/token (the
              S-proportional roofline term) so the 2x+ bandwidth win shows
              up in the perf trajectory, and the kernel/fallback runs are
              checked token-identical under greedy sampling.

plus the paged-cache mix (DESIGN.md §10):

  sharedprefix — N requests drawn from K distinct system prompts, served
              by the paged engine: every non-first request of a prompt
              group should hit the radix prefix index and skip its system
              prompt's prefill. Rows record prefill tokens skipped and KV
              bytes per resident token (pool bytes over deduplicated
              resident tokens); the bench's own expected hit count must
              agree with ``ServeEngine.stats()``, and the paged streams
              are checked token-identical to the dense engine's.

Rows land in experiments/bench/serve_engine.csv. Run standalone
(``python -m benchmarks.bench_serve_engine [--use-kernel]
[--kv-quant fxp8]``) or via ``benchmarks.run``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCHS, RunConfig, smoke as smoke_cfg
from repro.core.policy import format_spec, parse_kv_spec
from repro.launch.engine import Request, SamplingParams, ServeEngine
from repro.nn.models import (apply_policy, build_model,
                             kv_decode_bytes_per_token)

from .common import write_csv

ARCH = "yi-9b"


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_req: int = 8
    slots: int = 4
    prompt: int = 32
    gen: int = 16
    chunk: int = 8
    long_prompt: int = 96     # "long" for a CPU smoke model; the modeled
    long_gen: int = 16        # bytes/token ratio is context-length-invariant


FULL = Sizes()
# --smoke / tests/test_bench_smoke.py: every mix, variant and claim still
# runs — just few enough tokens that bench bit-rot fails in CI seconds
SMOKE = Sizes(n_req=4, slots=2, prompt=8, gen=6, chunk=4,
              long_prompt=16, long_gen=4)


def _mix_requests(mix: str, vocab: int, sz: Sizes) -> list:
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(sz.n_req):
        gen = sz.gen
        arrival = 0.0
        if mix == "staggered":
            arrival = float(i * (sz.gen // 2))
        elif mix == "ragged":
            gen = sz.gen // 2 if i % 2 else sz.gen
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, sz.prompt), max_new=gen,
            sampling=SamplingParams(), arrival=arrival))
    return reqs


def _longctx_requests(vocab: int, sz: Sizes) -> list:
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, sz.long_prompt),
                    max_new=sz.long_gen, sampling=SamplingParams(),  # greedy
                    arrival=float(i * (sz.long_gen // 2)))
            for i in range(sz.n_req)]


def _run_longctx(cfg, params, kv_spec, kv_kernel, use_kernel, sz: Sizes):
    model = build_model(cfg, RunConfig(remat="none"), use_kernel=use_kernel,
                        kv_spec=kv_spec, kv_kernel=kv_kernel)
    engine = ServeEngine(model, params, n_slots=sz.slots,
                         max_len=sz.long_prompt + sz.long_gen, chunk=sz.chunk,
                         seed=0)
    done = engine.run(_longctx_requests(cfg.vocab_size, sz))
    st = engine.stats()
    outs = {s.req.rid: list(s.out) for s in done}
    return st, outs


def _longctx_kv_spec(kv_quant: str):
    tok = (kv_quant or "").strip().lower()
    kv_spec = None if tok in ("none", "off") else parse_kv_spec(tok)
    if kv_spec is None:
        # "bf16"/"none" would run the bf16 cache three times and record it
        # under quantized-variant labels, polluting the perf trajectory
        raise ValueError(
            "the longctx mix measures the quantized KV cache: --kv-quant "
            f"must be a byte-wide fxp/pofx spec (e.g. fxp8, pofx8es2), "
            f"got {kv_quant!r}")
    return kv_spec


def run_longctx(cfg, params, kv_spec, use_kernel: bool, sz: Sizes = FULL):
    """Long-context arrival mix: bf16 cache vs quantized cache (XLA
    fallback and fused kernel). Returns (rows, claims)."""
    ctx_len = sz.long_prompt + sz.long_gen
    bf16 = kv_decode_bytes_per_token(cfg, ctx_len, None)
    rows, outs_by_variant = [], {}
    variants = [("bf16", None, False),
                ("xla-fallback", kv_spec, False),
                ("fused-kernel", kv_spec, True)]
    for name, spec, kern in variants:
        st, outs = _run_longctx(cfg, params, spec, kern, use_kernel, sz)
        if spec is not None:   # identity check is kernel-vs-fallback only
            outs_by_variant[name] = outs
        traffic = kv_decode_bytes_per_token(cfg, ctx_len, spec)
        rows.append({
            "mix": "longctx", "arch": ARCH, "quant": "(shared)",
            "use_kernel": use_kernel, "slots": sz.slots,
            "requests": sz.n_req,
            "prompt_len": sz.long_prompt, "gen": sz.long_gen,
            "generated_tokens": st["generated_tokens"],
            "decode_steps": st["decode_steps"],
            "decode_tok_per_s": round(
                st["decode_tokens"] / max(st["decode_time_s"], 1e-9), 2),
            "prefill_s": round(st["prefill_time_s"], 4),
            "decode_s": round(st["decode_time_s"], 4),
            "kv_variant": name,
            "kv_spec": format_spec(spec) if spec else "bf16",
            "kv_hbm_bytes_per_token": traffic["code_bytes"],
            "kv_scale_bytes_per_step": traffic["scale_bytes"],
        })
    quant_bytes = rows[1]["kv_hbm_bytes_per_token"]
    identical = outs_by_variant["xla-fallback"] == outs_by_variant["fused-kernel"]
    if not identical:
        # must be loud: the acceptance contract is token-identity between
        # the fused kernel and the quantize/dequantize fallback
        raise AssertionError(
            "kv flash-decode kernel and XLA fallback disagree under greedy "
            f"sampling: {outs_by_variant['fused-kernel']} vs "
            f"{outs_by_variant['xla-fallback']}")
    claims = {
        "kv_hbm_bytes_ratio": round(bf16["code_bytes"] / quant_bytes, 3),
        "kv_kernel_token_identical": identical,
    }
    return rows, claims


def run_sharedprefix(cfg, params, kv_spec, use_kernel: bool, sz: Sizes,
                     k_prompts: int = 2, page_size: int = 8):
    """Shared-system-prompt mix through the paged engine (DESIGN.md §10).

    N requests over K distinct system prompts (each 3/4 of the prompt
    length, so it spans whole pages plus a partial tail — the CoW path);
    the paged engine must skip the shared prefill, agree with the bench's
    own expected hit count, and stay token-identical to the dense engine.
    Returns (rows, claims).
    """
    rng = np.random.default_rng(11)
    sys_len = max(page_size, 3 * sz.prompt // 4)
    sys_prompts = [rng.integers(0, cfg.vocab_size, sys_len)
                   for _ in range(k_prompts)]
    prompts = []
    for i in range(sz.n_req):
        tail = rng.integers(0, cfg.vocab_size, max(1, sz.prompt - sys_len))
        prompts.append(np.concatenate([sys_prompts[i % k_prompts], tail]))
    # the last request repeats request 0's FULL prompt (an identical
    # retry): its index hit caps at context-1, which lands mid-page, so
    # the mix exercises the copy-on-write path too (asserted below);
    # distinct-tail requests share only whole system-prefix pages (the
    # radix index is page-granular on full pages)
    prompts[-1] = prompts[0]

    def reqs():
        return [Request(rid=i, prompt=prompts[i], max_new=sz.gen,
                        sampling=SamplingParams(),
                        arrival=float(i * (sz.gen // 2)))
                for i in range(sz.n_req)]

    workload = reqs()
    # f32 activations pin the dense-vs-paged identity assertion the same
    # way DESIGN.md §9/§10 pin the TP and sharing differentials: a
    # prefix-hit admission prefills only the suffix rows, and at bf16 the
    # different reduction tiling can flip a boundary-straddling token
    model = build_model(cfg, RunConfig(remat="none",
                                       activation_dtype="f32"),
                        use_kernel=use_kernel, kv_spec=kv_spec)
    max_len = sz.prompt + sz.gen
    dense = ServeEngine(model, params, n_slots=sz.slots, max_len=max_len,
                        chunk=sz.chunk, seed=0)
    ref = {s.req.rid: list(s.out) for s in dense.run([
        dataclasses.replace(r) for r in workload])}
    engine = ServeEngine(model, params, n_slots=sz.slots, max_len=max_len,
                         chunk=sz.chunk, seed=0, paged=True,
                         page_size=page_size)
    done = engine.run(workload)
    outs = {s.req.rid: list(s.out) for s in done}
    if outs != ref:
        raise AssertionError(
            f"paged engine diverges from dense on the sharedprefix mix: "
            f"{outs} vs {ref}")
    st = engine.stats()
    # admissions are serialized on the host, so every request after the
    # first of its prompt group must hit the index (>= the page-aligned
    # system prefix; CoW extends the hit into the shared partial page)
    expected_hits = sz.n_req - k_prompts
    if st["prefix_hits"] != expected_hits:
        raise AssertionError(
            f"prefix-cache hit count disagrees with the workload: engine "
            f"reports {st['prefix_hits']}, bench expects {expected_hits} "
            f"({sz.n_req} requests over {k_prompts} prompts)")
    # bytes of pool HBM per deduplicated resident token: per-token K+V
    # code bytes x page-internal fragmentation (allocated page slots over
    # distinct resident tokens) — the capacity half of the paging win
    per_tok = kv_decode_bytes_per_token(cfg, 1, kv_spec)["code_bytes"]
    resident = max(st["index_resident_tokens"], 1)
    row = {
        "mix": "sharedprefix", "arch": ARCH, "quant": "(shared)",
        "use_kernel": use_kernel, "slots": sz.slots,
        "requests": sz.n_req, "prompt_len": sz.prompt, "gen": sz.gen,
        "generated_tokens": st["generated_tokens"],
        "decode_steps": st["decode_steps"],
        "decode_tok_per_s": round(
            st["decode_tokens"] / max(st["decode_time_s"], 1e-9), 2),
        "prefill_s": round(st["prefill_time_s"], 4),
        "decode_s": round(st["decode_time_s"], 4),
        "kv_variant": f"paged-{page_size}",
        "kv_spec": format_spec(kv_spec) if kv_spec else "bf16",
        "prefill_tokens_skipped": st["prefix_hit_tokens"],
        "prefix_hit_rate": round(st["prefix_hit_rate"], 3),
        "resident_pages": st["resident_pages"],
        "kv_bytes_per_resident_token": round(
            per_tok * st["resident_pages"] * page_size / resident, 1),
        "cow_copies": st["cow_copies"],
    }
    claims = {
        "sharedprefix_prefill_tokens_skipped": int(st["prefix_hit_tokens"]),
        "sharedprefix_hits_agree": True,
        "sharedprefix_token_identical": True,
    }
    if st["prefix_hit_tokens"] <= 0:
        raise AssertionError(
            "sharedprefix mix skipped no prefill tokens: prefix sharing "
            "is not engaging")
    if st["cow_copies"] <= 0:
        raise AssertionError(
            "sharedprefix mix triggered no copy-on-write: the mis-aligned "
            "system prefix should end mid-page on every hit")
    return [row], claims


def run(use_kernel: bool = False, quant: str = "pofx8",
        kv_quant: str = "fxp8", smoke: bool = False):
    sz = SMOKE if smoke else FULL
    kv_spec = _longctx_kv_spec(kv_quant)   # fail fast, before engine work
    cfg = smoke_cfg(ARCHS[ARCH])
    model = build_model(cfg, RunConfig(remat="none"), use_kernel=use_kernel)
    params = apply_policy(model.init(jax.random.PRNGKey(0)), quant)
    rng = np.random.default_rng(7)
    rows = []
    for mix in ("burst", "staggered", "ragged"):
        reqs = _mix_requests(mix, cfg.vocab_size, sz)
        engine = ServeEngine(model, params, n_slots=sz.slots,
                             max_len=sz.prompt + sz.gen, chunk=sz.chunk,
                             seed=0)
        # warmup on the SAME engine (jit caches are per-instance): compile
        # prefill + the chunk variants outside the timed run, else the
        # first mix absorbs all XLA compile time and the mix comparison
        # becomes a measurement artifact
        engine.run([Request(rid=1000 + i,
                            prompt=rng.integers(0, cfg.vocab_size, sz.prompt),
                            max_new=sz.gen, sampling=SamplingParams())
                    for i in range(sz.slots)])
        engine.prefill_time = engine.decode_time = 0.0
        engine.decode_steps = 0
        engine.clock = 0.0  # warmup must not shift the measured arrivals
        warm_gen = engine.stats()["generated_tokens"]
        warm_sampled = engine.n_prefill_sampled
        engine.run(reqs)
        st = engine.stats()
        n_gen = st["generated_tokens"] - warm_gen
        n_dec = n_gen - (engine.n_prefill_sampled - warm_sampled)
        rows.append({
            "mix": mix, "arch": ARCH, "quant": quant,
            "use_kernel": use_kernel, "slots": sz.slots,
            "requests": sz.n_req,
            "prompt_len": sz.prompt, "gen": sz.gen,
            "generated_tokens": n_gen,
            "decode_steps": st["decode_steps"],
            "decode_tok_per_s": round(n_dec / max(st["decode_time_s"], 1e-9),
                                      2),
            "prefill_s": round(st["prefill_time_s"], 4),
            "decode_s": round(st["decode_time_s"], 4),
        })
    by_mix = {r["mix"]: r["decode_tok_per_s"] for r in rows}
    claims = {
        f"decode_tok_per_s[{m}]": v for m, v in by_mix.items()
    }
    claims["staggered_vs_burst_ratio"] = round(
        by_mix["staggered"] / max(by_mix["burst"], 1e-9), 3)
    # persist the arrival mixes before the longctx runs: the loud
    # kernel-vs-fallback identity assertion must not discard them
    write_csv("serve_engine", rows)
    long_rows, long_claims = run_longctx(cfg, params, kv_spec, use_kernel,
                                         sz)
    rows += long_rows
    claims.update(long_claims)
    write_csv("serve_engine", rows)
    sp_rows, sp_claims = run_sharedprefix(cfg, params, kv_spec, use_kernel,
                                          sz, page_size=2 if smoke else 8)
    rows += sp_rows
    claims.update(sp_claims)
    write_csv("serve_engine", rows)
    return rows, claims


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--quant", default="pofx8")
    ap.add_argument("--kv-quant", default="fxp8",
                    help="KV-cache format for the longctx mix (fxp/pofx, "
                         "byte-wide codes)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: bit-rot check, not a measurement")
    args = ap.parse_args(argv)
    rows, claims = run(use_kernel=args.use_kernel, quant=args.quant,
                       kv_quant=args.kv_quant, smoke=args.smoke)
    for r in rows:
        print(r)
    for k, v in claims.items():
        print(f"serve_engine,{k},{v}")


if __name__ == "__main__":
    main()
