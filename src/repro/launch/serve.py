"""Serving driver: continuous-batching quantized serving through the engine.

The paper's deployment story end to end: weights post-training-quantized per
a QuantPolicy — one format (``--quant pofx8es2``) or mixed per-layer formats
(``--quant "attn/*=pofx8es2,mlp/*=fxp8f7,*=bf16"``) — served by the
slot-based continuous-batching engine (``repro.launch.engine``): per-request
admission, scan-fused multi-token decode with per-slot stopping, pluggable
sampling. ``--use-kernel`` routes every quantized matmul through the fused
Pallas PoFx/FxP kernels (the paper's Move&Store accelerator datapath;
interpret mode on CPU), so quantized serving actually exercises them.
``--kv-quant fxp8`` (or a ``kv=fxp8`` rule inside ``--quant``) additionally
stores the decode KV cache as quantization codes and — with
``--use-kernel`` — attends through the fused Pallas flash-decode kernel,
cutting the S-proportional decode HBM term 2x+ (DESIGN.md §8):

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \\
        --quant pofx8 --kv-quant fxp8 --use-kernel

Token accounting: ``--gen`` is the number of tokens *generated per request*
(the first comes from the prefill logits, the remaining ``gen-1`` from
decode steps); the decode tok/s rate divides decode-generated tokens by
decode wall time, and the printed sample has exactly ``gen`` tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
        --quant pofx8 --use-kernel --prompt-len 64 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
        --temperature 0.8 --top-k 40 --arrival-gap 8 --requests 12

``--paged --page-size N`` serves through the paged KV cache (DESIGN.md
§10): a flat pool of N-token pages + per-slot block tables + a radix
prefix index, so shared system prompts skip their prefill and eviction
keeps pages resident (resume re-prefills one token). Token streams are
identical to the dense engine's; the summary adds a paging-metrics line
(prefix hit rate, resident pages, pages freed, CoW copies):

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \\
        --paged --page-size 16 --kv-quant fxp8 --use-kernel

``--legacy`` (automatic for encdec, which needs per-batch encoder frames)
runs the old one-shot fixed-batch greedy loop instead.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, RunConfig, smoke as smoke_cfg
from repro.core.policy import (QuantPolicy, add_kv_quant_arg, add_policy_arg,
                               format_spec, resolve_kv_spec, storage_report)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import Request, SamplingParams, ServeEngine
from repro.launch.mesh import make_tp_mesh
from repro.nn.models import build_model, kv_decode_bytes_per_token

# Back-compat name; the policy-aware report lives in repro.core.policy.
param_storage_report = storage_report


def build_served_model(cfg, policy, kv_spec, *, use_kernel: bool,
                       tp: int = 1, seed: int = 0):
    """(model, params): the served model and its quantized parameters.

    Parameters come from ``seed`` and are made and quantized one layer at a
    time on the device (``LM.init_quantized``), so a model whose float
    weights would not fit (yi-9b: 17.7 GB in bf16) serves at its published
    width from its codes (~8.8 GB as pofx8). With ``tp > 1`` they come out
    sharded over the ("tp",) mesh.
    """
    mesh = make_tp_mesh(tp) if tp > 1 else None
    model = build_model(cfg, RunConfig(remat="none"), mesh=mesh,
                        use_kernel=use_kernel, kv_spec=kv_spec)
    return model, model.init_quantized(jax.random.PRNGKey(seed), policy)


def memory_report(device=None) -> str:
    """One line of ``device.memory_stats()``: bytes in use and the peak."""
    stats = (device or jax.devices()[0]).memory_stats()
    if not stats:
        return "device memory: not reported by this backend"
    return (f"device memory: {stats['bytes_in_use'] / 1e9:.2f} GB in use, "
            f"peak {stats['peak_bytes_in_use'] / 1e9:.2f} GB")


def _legacy_main(args, cfg, model, params) -> None:
    """One-shot fixed-batch greedy serving (the encdec path).

    Generates exactly ``args.gen`` tokens per sequence: 1 sampled from the
    prefill logits + ``gen-1`` decode steps — the reported rates divide by
    the matching counts (the old driver concatenated ``gen+1`` tokens while
    dividing by ``gen``).
    """
    B, P = args.batch, args.prompt_len
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.vocab_size)
    frames = None
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2), (B, P, cfg.d_model),
                                   jnp.float32)
    max_len = P + args.gen
    cache = model.init_cache(B, max_len, enc_len=P)

    t0 = time.perf_counter()
    # frames is a real jit argument (not a closed-over constant): a new
    # encoder batch must not silently reuse the baked-in prefill trace.
    cache, logits = jax.jit(
        lambda p, c, t, f: model.prefill(p, t, cache=c, frames=f),
        donate_argnums=(1,))(params, cache, prompts, frames)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    tok = jnp.argmax(logits, axis=-1)[:, None]
    outs = [tok]
    n_steps = args.gen - 1
    t0 = time.perf_counter()
    for _ in range(n_steps):
        cache, logits = decode(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1)[:, None]
        outs.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.asarray(jnp.concatenate(outs, axis=1))
    assert not np.any(np.isnan(np.asarray(logits))), "NaN logits"
    print(f"prefill: {B}x{P} tokens in {t_prefill:.3f}s "
          f"({B*P/t_prefill:.0f} tok/s, +1 sampled token/seq)")
    if n_steps:
        print(f"decode:  {n_steps} steps x {B} seqs in {t_decode:.3f}s "
              f"({n_steps*B/t_decode:.1f} tok/s)")
    print(f"total:   {args.gen} tokens/seq x {B} seqs")
    print(f"sample ({gen.shape[1]} tokens):", gen[0, :16].tolist(),
          "..." if gen.shape[1] > 16 else "")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="yi-9b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    add_policy_arg(ap, default="pofx8")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route quantized matmuls through the fused Pallas "
                         "PoFx/FxP kernels, and quantized-KV decode through "
                         "the fused flash-decode kernel (interpret mode on "
                         "CPU)")
    add_kv_quant_arg(ap)
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (legacy: fixed batch size)")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to serve (default: 2x slots)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32,
                    help="tokens generated per request")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="0 = off")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop token id (<0 = none; random-weight demos "
                         "never stop early)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps fused into one scan")
    ap.add_argument("--arrival-gap", type=float, default=0.0,
                    help="virtual decode steps between request arrivals "
                         "(0 = all at once)")
    ap.add_argument("--prompt-bucket", type=int, default=1,
                    help="round prompt lengths up to this multiple for "
                         "prefill (bounds recompilation; attention "
                         "families only)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (DESIGN.md §10): fixed-size token "
                         "pages + per-slot block tables + radix prefix "
                         "sharing; token streams identical to the dense "
                         "engine (attention families dense/moe only)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page pool size (--paged; 0 = dense-equivalent "
                         "capacity + headroom)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices (1-D 'tp' mesh): shards "
                         "attention heads / MLP hidden / experts and the KV "
                         "cache's head axis over N devices; greedy outputs "
                         "are token-identical to --tp 1 (DESIGN.md §9; CPU: "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--legacy", action="store_true",
                    help="one-shot fixed-batch greedy loop (no engine)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = smoke_cfg(cfg)
    policy = QuantPolicy.from_string(args.quant)
    kv_spec = resolve_kv_spec(args.kv_quant, policy)
    if kv_spec is not None and cfg.family == "encdec":
        print("(encdec: quantized KV cache unsupported on the legacy "
              "one-shot path; serving with a bf16 cache)")
        kv_spec = None
    if args.tp > 1 and (args.legacy or cfg.family == "encdec"):
        ap.error("--tp needs the engine path (not --legacy / encdec)")
    if args.paged and (args.legacy or cfg.family == "encdec"):
        ap.error("--paged needs the engine path (not --legacy / encdec)")
    model, params = build_served_model(cfg, policy, kv_spec,
                                       use_kernel=args.use_kernel, tp=args.tp)
    print(f"[{args.arch} quant={policy.to_string()} "
          f"kv={format_spec(kv_spec) if kv_spec else 'bf16'} "
          f"kernel={'pallas' if args.use_kernel else 'xla-lut'}"
          f"{f' tp={args.tp}' if args.tp > 1 else ''}]")
    print(storage_report(params, policy))
    print(memory_report())
    ctx_len = args.prompt_len + args.gen
    kv_q = kv_decode_bytes_per_token(cfg, ctx_len, kv_spec)
    kv_b = kv_decode_bytes_per_token(cfg, ctx_len, None)
    if kv_spec is not None and kv_q["code_bytes"]:
        print(f"  kv cache @ {ctx_len} ctx: "
              f"{kv_q['code_bytes'] / 2**10:.1f} KiB/token streamed "
              f"(+{kv_q['scale_bytes'] / 2**10:.1f} KiB static scales) vs "
              f"bf16 {kv_b['code_bytes'] / 2**10:.1f} KiB "
              f"({kv_b['code_bytes'] / kv_q['code_bytes']:.1f}x less decode "
              f"HBM traffic)")

    if args.legacy or cfg.family == "encdec":
        if not args.legacy:
            print("(encdec: engine unsupported, using one-shot path)")
        ignored = [f for f, on in (
            ("--temperature", args.temperature != 0.0),
            ("--top-k", args.top_k != 0),
            ("--requests", args.requests != 0),
            ("--arrival-gap", args.arrival_gap != 0.0),
            ("--prompt-bucket", args.prompt_bucket > 1),
            ("--eos-id", args.eos_id >= 0),
            ("--chunk", args.chunk != 8)) if on]
        if ignored:
            print(f"(legacy path is greedy fixed-batch; ignoring "
                  f"{', '.join(ignored)})")
        _legacy_main(args, cfg, model, params)
        return

    P, G = args.prompt_len, args.gen
    n_req = args.requests or 2 * args.batch
    if n_req < 1 or G < 1 or P < 1:
        ap.error("--requests/--gen/--prompt-len must be >= 1")
    if args.paged and cfg.family not in ("dense", "moe"):
        ap.error(f"--paged supports dense/moe families, not {cfg.family}")
    engine = ServeEngine(
        model, params, n_slots=args.batch, max_len=P + G,
        eos_id=args.eos_id if args.eos_id >= 0 else None,
        chunk=args.chunk, prompt_bucket=args.prompt_bucket, seed=0,
        paged=args.paged, page_size=args.page_size,
        n_pages=args.n_pages or None)
    rng = np.random.default_rng(1)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k)
    requests = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, P),
                max_new=G, sampling=sampling, arrival=i * args.arrival_gap)
        for i in range(n_req)
    ]
    done = engine.run(requests)

    stats = engine.stats()
    spans = stats["spans"]
    n_prefill_tok = stats["prefill_tokens"]
    n_gen = stats["generated_tokens"]
    n_dec = stats["decode_tokens"]      # excludes prefill-sampled tokens
    print(f"served {len(done)} requests on {args.batch} slots "
          f"(chunk={args.chunk}, arrival gap={args.arrival_gap} steps)")
    print(f"prefill: {n_prefill_tok} tokens prefilled (of "
          f"{stats['admitted_prompt_tokens']} admitted) in "
          f"{engine.prefill_time:.3f}s ({n_prefill_tok/engine.prefill_time:.0f}"
          f" tok/s, +{stats['prefill_sampled_tokens']} sampled tokens)")
    print(f"decode:  {engine.decode_steps} scan steps, {n_dec} tokens in "
          f"{engine.decode_time:.3f}s ({n_dec/max(engine.decode_time,1e-9):.1f}"
          f" tok/s)")
    total_s = spans["serve.run"]["s"]
    print(f"total:   {n_gen} generated tokens in {total_s:.3f}s "
          f"({n_gen/total_s:.1f} tok/s end-to-end)")
    print("spans:   " + ", ".join(f"{name} {v['n']}x {v['s']:.3f}s"
                                  for name, v in sorted(spans.items())))
    if args.paged:
        print(f"paging:  page={engine.page_size} tok, "
              f"{stats['resident_pages']}/{engine.n_pages - 1} pages "
              f"resident, prefix hits {stats['prefix_hits']}/"
              f"{stats['prefix_queries']} "
              f"(rate {stats['prefix_hit_rate']:.2f}, "
              f"{stats['prefix_hit_tokens']} prefill tokens skipped), "
              f"{stats['pages_freed']} pages freed on evict/finish, "
              f"{stats['cow_copies']} CoW copies")
    s0 = done[0]
    if any(len(s.out) > G for s in done):  # must survive `python -O`
        raise RuntimeError("engine generated more than --gen tokens")
    print(f"sample rid=0 ({len(s0.out)} tokens, {s0.finish_reason}):",
          s0.out[:16], "..." if len(s0.out) > 16 else "")


if __name__ == "__main__":
    main()
