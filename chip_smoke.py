"""Bring-up check on a TPU: serve yi-9b at its published width.

Drives the serving path once the way ``python -m repro.launch.serve`` does
(``build_served_model`` -> ``ServeEngine``) at yi-9b's published width: 48
layers, d_model 4096, 32 query / 4 KV heads, d_ff 11008, vocab 64000. The
weights are random, made from a seed, and stored as pofx8 codes (about
8.8 GB; the bf16 model, 17.7 GB, does not fit one 16 GB v5e). The KV cache
holds fxp8 codes and the Pallas kernels run compiled, not interpreted.
The same parameters then go, in a twin of the model with f32 activations,
through the XLA path and the paged cache, and the logits are compared.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips: tp=4 against tp=1, nothing else

Exits non-zero, printing no result, when JAX finds no TPU. On success the
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times printed on the way include compilation: this is a bring-up
check, not a measurement.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ARCH = "yi-9b"
QUANT, KV_QUANT = "pofx8", "fxp8"
N_SLOTS, N_REQUESTS, PROMPT_LEN, GEN = 4, 8, 384, 32
SEED = 0
# Largest relative L2 error allowed between two paths' logits (per row).
# The comparisons run a twin of the served model with f32 activations and
# f32 matmuls (precision "highest"; on a TPU a default-precision f32 matmul
# rounds its operands to bf16), and no two compared paths write the fxp8 KV
# cache separately (see ``one_chip``). The paths then differ only in the
# order of their f32 sums. Over this architecture's 48 layers, at reduced
# width on CPU, that measured 2.0e-6 (prefill, kernels vs XLA and tp=4 vs
# tp=1), 3.1e-7 (prefill, paged vs dense) and 1.4e-5 (decode). A kernel
# that decodes one common code value one LSB off (4.9e-2), scales one output
# channel wrong (3.9e-1) or masks one position too few (5.4e-2) fails it
# (mutation checks on CPU, same setup).
# The bf16 engines that serve the requests are compared by token agreement
# only: rounding activations to bf16 at different points moves their
# logits by about 3.5%.
REL_BOUND = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Comparisons made so far; each prints one line."""

    def __init__(self):
        self.failed = []

    def logits(self, name: str, got, ref) -> None:
        import numpy as np
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        finite = bool(np.isfinite(got).all() and np.isfinite(ref).all())
        err = float(np.max(np.linalg.norm(got - ref, axis=-1)
                           / np.linalg.norm(ref, axis=-1)))
        ok = finite and err <= REL_BOUND
        log(f"compare {name}: max relative L2 error {err:.3e} "
            f"(bound {REL_BOUND}), finite={finite}: "
            f"{'ok' if ok else 'FAILED'}")
        if not ok:
            self.failed.append(name)

    def require(self, name: str, ok: bool, detail: str) -> None:
        log(f"check {name}: {detail}: {'ok' if ok else 'FAILED'}")
        if not ok:
            self.failed.append(name)


class CompileClock:
    """Seconds JAX has spent in backend compilation, from its own
    monitoring events: splits a wall time into compilation and the rest."""

    def __init__(self):
        import jax
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


def compiled(jitted, *args, kernels: bool, checks: Checks, name: str):
    """Compile ``jitted`` for ``args``; check that Pallas kernels are in
    the program exactly when ``kernels`` says so."""
    t0 = time.perf_counter()
    exe = jitted.lower(*args).compile()
    n = exe.as_text().count("tpu_custom_call")
    checks.require(f"{name} program", (n > 0) == kernels,
                   f"{n} tpu_custom_call ops, compiled in "
                   f"{time.perf_counter() - t0:.1f}s")
    return exe


def serve(model, params, prompts, clock: CompileClock, label: str, *,
          paged: bool = False):
    """Serve one greedy request per prompt through a fresh engine; returns
    (tokens per rid, engine)."""
    from repro.launch.engine import Request, ServeEngine
    t0, c0 = time.perf_counter(), clock.secs
    engine = ServeEngine(model, params, n_slots=N_SLOTS,
                         max_len=PROMPT_LEN + GEN, seed=SEED, paged=paged)
    done = engine.run([Request(rid=i, prompt=p, max_new=GEN)
                       for i, p in enumerate(prompts)])
    log(f"served {len(done)} requests x {GEN} tokens on {N_SLOTS} slots, "
        f"{label}: {time.perf_counter() - t0:.1f}s wall, of which "
        f"{clock.secs - c0:.1f}s compiling; admissions "
        f"{engine.prefill_time:.1f}s and {engine.decode_steps} decode steps "
        f"{engine.decode_time:.1f}s, first calls' compilation included")
    return {s.req.rid: s.out for s in done}, engine


def check_served(checks: Checks, label: str, outs, vocab: int) -> None:
    n_ok = sum(len(t) == GEN and all(0 <= x < vocab for x in t)
               for t in outs.values())
    checks.require(f"{label} served", len(outs) == N_REQUESTS
                   and n_ok == N_REQUESTS,
                   f"{n_ok}/{N_REQUESTS} requests finished with {GEN} "
                   f"in-vocabulary tokens")


def agreement(a, b) -> float:
    same = sum(x == y for rid in a for x, y in zip(a[rid], b[rid]))
    return same / sum(len(t) for t in a.values())


def f32_twin(model, **changes):
    """The same model with f32 activations (and ``changes``): it reads the
    same params."""
    return dataclasses.replace(model, rcfg=dataclasses.replace(
        model.rcfg, activation_dtype="f32"), **changes)


def paged_copy(cache, page_size: int):
    """The dense cache's contents as a page pool: slot b's tokens fill
    pages 1 + b * n .. (b + 1) * n of its block table, page 0 is the
    garbage page, and the per-slot static scales (equal across slots)
    become the pool's global ones. Dense family only."""
    import jax.numpy as jnp
    kv = cache["kv"]
    L, B, G, S, Dh = kv["k"].shape
    n = -(-S // page_size)

    def pool(x):
        x = jnp.pad(x, ((0, 0),) * 3 + ((0, n * page_size - S), (0, 0)))
        x = x.reshape(L, B, G, n, page_size, Dh).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(L, B * n, G, page_size, Dh)
        return jnp.concatenate([jnp.zeros_like(x[:, :1]), x], axis=1)

    return {"pos": cache["pos"],
            "pages": jnp.arange(1, B * n + 1, dtype=jnp.int32).reshape(B, n),
            "kv": {"k": pool(kv["k"]), "k_scale": kv["k_scale"][:, 0],
                   "v": pool(kv["v"]), "v_scale": kv["v_scale"][:, 0]}}


def one_chip(cfg, model, params, prompts, checks: Checks,
             clock: CompileClock) -> None:
    """Serve through the dense and the paged engine, then compare kernels
    against XLA and paged against dense on one set of params.

    Prefill is compared with a float KV cache: prefill writes the KV cache
    through the fxp8 grid, so two paths whose K/V differ in the last bit
    round a few codes apart, and over 48 layers that alone gave 1.0e-3
    (reduced width, on CPU). Decode is compared from one fxp8 cache, filled
    once, which is what the KV kernels read.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, P, max_len = N_REQUESTS, PROMPT_LEN, PROMPT_LEN + GEN
    outs, _ = serve(model, params, prompts, clock,
                    "dense cache, compiled kernels")
    check_served(checks, "dense engine", outs, cfg.vocab_size)
    log(f"sample rid=0: {outs[0][:16]} ...")
    paged_outs, _ = serve(model, params, prompts, clock,
                          "paged cache, compiled kernels", paged=True)
    check_served(checks, "paged engine", paged_outs, cfg.vocab_size)
    log(f"greedy tokens of the paged run agree with the dense run: "
        f"{agreement(paged_outs, outs):.4f} (bf16 activations)")

    exact = f32_twin(model)
    flt = f32_twin(model, kv_spec=None)
    tokens = jnp.asarray(np.stack(prompts))
    page_size = 16
    max_pages = -(-max_len // page_size)
    logits = {}
    with jax.default_matmul_precision("highest"):
        for name, m in (("kernel", flt),
                        ("xla", dataclasses.replace(flt, use_kernel=False))):
            cache = m.init_cache(B, max_len)
            prefill = compiled(
                jax.jit(lambda p, c, t, m=m: m.prefill(p, t, cache=c)),
                params, cache, tokens, kernels=name == "kernel",
                checks=checks, name=f"{name} prefill, float KV")
            _, logits[f"{name} prefill"] = prefill(params, cache, tokens)

        cache = flt.init_paged_cache(B, max_len, n_pages=B * max_pages + 1,
                                     page_size=page_size)
        cache["pages"] = jnp.arange(1, B * max_pages + 1,
                                    dtype=jnp.int32).reshape(B, max_pages)
        prefill = compiled(
            jax.jit(lambda p, c, t, s: flt.prefill_paged(
                p, t, cache=c, slot=s, length=jnp.int32(P)),
                donate_argnums=(1,)),
            params, cache, tokens[:1], jnp.int32(0), kernels=True,
            checks=checks, name="paged prefill, float KV")
        rows = []
        for i in range(B):
            cache, row = prefill(params, cache, tokens[i:i + 1], jnp.int32(i))
            rows.append(row)
        logits["paged prefill"] = jnp.concatenate(rows)

        cache = exact.init_cache(B, max_len)
        prefill = compiled(
            jax.jit(lambda p, c, t: exact.prefill(p, t, cache=c)),
            params, cache, tokens, kernels=True, checks=checks,
            name=f"kernel prefill, {KV_QUANT} KV")
        cache, first = prefill(params, cache, tokens)
        cache["pos"] = jnp.full((B,), P, jnp.int32)   # per-slot, as served
        nxt = jnp.argmax(first, -1)[:, None].astype(jnp.int32)
        for name, m in (("kernel", exact),
                        ("xla", dataclasses.replace(exact, use_kernel=False))):
            decode = compiled(jax.jit(m.decode_step), params, cache, nxt,
                              kernels=name == "kernel", checks=checks,
                              name=f"{name} decode")
            _, logits[f"{name} decode"] = decode(params, cache, nxt)
        paged = paged_copy(cache, page_size)
        decode = compiled(jax.jit(exact.decode_step), params, paged, nxt,
                          kernels=True, checks=checks, name="paged decode")
        _, logits["paged decode"] = decode(params, paged, nxt)

    for step in ("prefill", "decode"):
        checks.logits(f"{step} logits, kernels vs XLA",
                      logits[f"kernel {step}"], logits[f"xla {step}"])
        checks.logits(f"{step} logits, paged vs dense cache",
                      logits[f"paged {step}"], logits[f"kernel {step}"])


def prompt_logits(engine, prompts, checks: Checks, name: str):
    """Last-position logits of each prompt through the engine's own
    admission prefill (``_prefill_fn``: the shard_map-wrapped manual-TP
    twin over the engine's sharded params when it serves tensor-parallel)."""
    import jax.numpy as jnp
    import numpy as np

    def small():
        return engine._seed_kv_scales(
            engine.model.init_cache(1, engine.max_len), 0)

    tokens = [jnp.asarray(p[None]) for p in prompts]
    prefill = compiled(engine._prefill_fn, engine.params, small(), tokens[0],
                       None, kernels=True, checks=checks, name=name)
    return np.concatenate([
        np.asarray(prefill(engine.params, small(), t, None)[1], np.float32)
        for t in tokens])


def tensor_parallel(cfg, model, params, prompts, tp: int, checks: Checks,
                    clock: CompileClock) -> None:
    """yi-9b at tp against tp=1 on the same params, and nothing else.

    Both engines serve the f32-activation twin, as the repo's TP tests do:
    at bf16 a sharded sum rounds differently from the whole one. Prefill
    logits come from each engine's own prefill program with a float KV
    cache (see ``one_chip``). The tp=1 run comes first; then the params
    move to the host and leave the device, so that device 0 holds only its
    shard while tp serves. ``params`` is deleted on the way.
    """
    import gc

    import jax

    from repro.launch.engine import ServeEngine
    from repro.launch.mesh import make_tp_mesh
    from repro.nn.models import build_model

    exact = f32_twin(model)
    sharded = build_model(cfg, exact.rcfg, mesh=make_tp_mesh(tp),
                          use_kernel=True, kv_spec=model.kv_spec)

    def float_kv(m, p):
        return ServeEngine(dataclasses.replace(m, kv_spec=None), p,
                           n_slots=1, max_len=PROMPT_LEN + GEN)

    with jax.default_matmul_precision("highest"):
        ref, engine = serve(exact, params, prompts, clock,
                            "tp=1, f32 activations")
        check_served(checks, "tp=1 engine", ref, cfg.vocab_size)
        ref_logits = prompt_logits(float_kv(exact, params), prompts, checks,
                                   "tp=1 engine prefill, float KV")

        host = jax.device_get(params)
        for x in jax.tree_util.tree_leaves(params):
            x.delete()
        del engine
        gc.collect()
        outs, engine = serve(sharded, host, prompts, clock,
                             f"tp={tp}, f32 activations")
        del host
        check_served(checks, f"tp={tp} engine", outs, cfg.vocab_size)
        log(f"greedy tokens at tp={tp} agree with tp=1: "
            f"{agreement(outs, ref):.4f}")
        tp_logits = prompt_logits(float_kv(sharded, engine.params), prompts,
                                  checks, f"tp={tp} engine prefill, float KV")
    checks.logits(f"prefill logits, tp={tp} vs tp=1", tp_logits, ref_logits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips (tp=N against tp=1)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if args.tp > len(devices):
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import numpy as np

    from repro.configs import ARCHS
    from repro.core.policy import QuantPolicy, resolve_kv_spec, storage_report
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_served_model, memory_report

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = ARCHS[ARCH]
    policy = QuantPolicy.from_string(QUANT)
    kv_spec = resolve_kv_spec(KV_QUANT, policy)
    log(f"[{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; weights {QUANT}, kv {KV_QUANT}]")
    t0 = time.perf_counter()
    model, params = build_served_model(cfg, policy, kv_spec, use_kernel=True,
                                       seed=SEED)
    # dispatch is asynchronous: without this wait the encode's device time
    # would land in whichever later phase first waits on the params
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"params made and quantized on the device: "
        f"{n_bytes / 1e9:.3f} GB of codes, scales and norms; "
        f"{time.perf_counter() - t0:.1f}s wall, of which {clock.secs:.1f}s "
        f"compiling")
    log(storage_report(params, policy))
    log(memory_report(dev))

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
               for _ in range(N_REQUESTS)]
    checks = Checks()
    if args.tp > 1:
        tensor_parallel(cfg, model, params, prompts, args.tp, checks, clock)
    else:
        one_chip(cfg, model, params, prompts, checks, clock)
    log(memory_report(dev))
    if checks.failed:
        print(f"chip_smoke: failed: {', '.join(checks.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
