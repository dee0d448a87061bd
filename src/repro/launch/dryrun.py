import os
if __name__ == "__main__":
    # 512 fake host devices for the production meshes: only when run as a
    # program, never when imported.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs from the compiled
artifact. (The XLA_FLAGS line above MUST run before any jax import — jax
locks the device count on first init.)

Per cell this driver:
  1. builds the model + abstract state (ShapeDtypeStruct, no allocation),
  2. jits the right step (train_step / prefill / decode_step) with explicit
     in/out shardings and donation,
  3. ``.lower().compile()`` on the (16,16) single-pod or (2,16,16)
     multi-pod mesh — success IS the deliverable,
  4. prints ``compiled.memory_analysis()`` / ``cost_analysis()`` and parses
     collective bytes from the post-SPMD HLO (hlo_analysis.py),
  5. writes a JSON record under experiments/dryrun/ for §Roofline.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--force]

``--all`` runs each cell in a fresh subprocess (compile-memory isolation;
a crash or OOM in one cell cannot take down the sweep) and skips cells
whose JSON already exists.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

__all__ = ["run_cell", "main"]

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# Per-arch training knobs chosen by napkin math over v5e HBM (16 GB/chip):
# microbatches bound the per-layer activation checkpoints; SP shards the
# residual stream over the model axis; posit8 moments (the paper's codec on
# optimizer state) halve the Adam footprint for the 100B+ models.
TRAIN_KNOBS: Dict[str, Dict[str, Any]] = {
    "llama3-405b":              dict(microbatch=8, sequence_parallel=True, opt="posit8"),
    "nemotron-4-340b":          dict(microbatch=8, sequence_parallel=True, opt="posit8"),
    "deepseek-67b":             dict(microbatch=4, sequence_parallel=True, opt="none"),
    "chameleon-34b":            dict(microbatch=2, sequence_parallel=True, opt="none"),
    "yi-9b":                    dict(microbatch=1, sequence_parallel=True, opt="none"),
    "llama4-maverick-400b-a17b": dict(microbatch=2, sequence_parallel=True, opt="posit8"),
    "moonshot-v1-16b-a3b":      dict(microbatch=1, sequence_parallel=True, opt="none"),
    "chameleon-7b":             dict(microbatch=1, sequence_parallel=True, opt="none"),
    "falcon-mamba-7b":          dict(microbatch=1, sequence_parallel=False, opt="none"),
    "whisper-medium":           dict(microbatch=1, sequence_parallel=True, opt="none"),
    "zamba2-1.2b":              dict(microbatch=1, sequence_parallel=False, opt="none"),
}


def _cell_path(out_dir: str, arch: str, shape: str, mesh_kind: str,
               quant: str) -> str:
    import re
    safe = re.sub(r"[^A-Za-z0-9._-]+", "-", quant)  # policy strings have */=,
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}__{safe}.json")


def analytic_model_flops(cfg, shape) -> float:
    """The spec's MODEL_FLOPS convention: 6*N*D train, 2*N*D forward-only."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n * tokens


def _trip_counts(model, cfg, rcfg, shape) -> list:
    if cfg.family == "moe":
        L = cfg.n_layers // cfg.moe_every
    else:
        L = cfg.n_layers
    trips = []
    if shape.kind == "train" and rcfg.microbatch > 1:
        trips.append(rcfg.microbatch)
    trips.append(L)
    if shape.kind in ("train", "prefill"):
        if cfg.family in ("ssm", "hybrid"):
            inner = max(shape.seq_len // cfg.ssm_chunk, 1)
        else:
            inner = max(shape.seq_len // rcfg.attn_kv_chunk, 1)
        trips.append(inner)
    return trips


def run_cell(arch: str, shape_name: str, mesh_kind: str = "single",
             quant: str = "auto", out_dir: str = OUT_DIR,
             verbose: bool = True) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS, LONG_CONTEXT_ARCHS, RunConfig
    from repro.configs.base import SHAPES
    from repro.core.policy import QuantPolicy
    from repro.core.quantizers import QuantizedTensor
    from repro.launch import hlo_analysis, hlo_parser
    from repro.launch.mesh import make_production_mesh
    from repro.launch.train import (abstract_train_state, batch_shardings,
                                    make_train_step, state_shardings)
    from repro.nn.models import apply_policy, build_model, input_specs

    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "quant": quant,
                           "kind": shape.kind}
    if shape.name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        rec.update(ok=False, skipped=True,
                   reason="full-attention arch: long_500k needs sub-quadratic mixing")
        return rec

    knobs = TRAIN_KNOBS.get(arch, {})
    if shape.kind == "train":
        rcfg = RunConfig(remat="block",
                         microbatch=knobs.get("microbatch", 1),
                         sequence_parallel=knobs.get("sequence_parallel", False),
                         opt_state_quant=knobs.get("opt", "none"))
    else:
        rcfg = RunConfig(remat="none", sequence_parallel=False,
                         serve_bf16_compute=True)
    if quant == "auto":
        quant = "bf16" if shape.kind == "train" else "pofx8"
        rec["quant"] = quant
    # a kv= rule in the policy string sizes/lowers the quantized decode
    # cache (code+scale leaves) through the XLA fallback path — the kernel
    # is validated separately and kept out of the huge dry-run graphs
    kv_spec = None
    if shape.kind != "train" and quant not in ("bf16", "fp32") \
            and cfg.family != "encdec":
        kv_spec = QuantPolicy.from_string(quant).kv_spec
        rec["kv_quant"] = bool(kv_spec)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    model = build_model(cfg, rcfg, mesh=mesh, kv_spec=kv_spec,
                        kv_kernel=False)
    repl = NamedSharding(mesh, P())

    t0 = time.time()
    batch_abs = input_specs(cfg, shape)

    if shape.kind == "train":
        state_abs = abstract_train_state(model)
        ss = state_shardings(model, state_abs)
        bs = batch_shardings(model, batch_abs)
        step = make_train_step(model, mesh)
        jitted = jax.jit(step, in_shardings=(ss, bs),
                         out_shardings=(ss, None), donate_argnums=(0,))
        args = (state_abs, batch_abs)
    else:
        # serving: weights quantized per the --quant policy string — one
        # format ("pofx8es2") or mixed rules ("attn/*=pofx8es2,*=bf16");
        # decode cache sharded + donated. Quantized leaves keep their codes
        # replicated-scale sharding tree structure (QuantizedTensor nodes).
        p_abs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        p_shard = model.param_shardings(p_abs)
        if quant not in ("bf16", "fp32"):
            policy = QuantPolicy.from_string(quant)
            p_abs = jax.eval_shape(
                lambda: apply_policy(model.init(jax.random.PRNGKey(0)),
                                     policy))
            flat_s, td = jax.tree_util.tree_flatten(
                p_shard, is_leaf=lambda x: x is None)
            objs = td.flatten_up_to(p_abs)
            flat_q = [QuantizedTensor(s, repl, o.spec)
                      if isinstance(o, QuantizedTensor) else s
                      for s, o in zip(flat_s, objs)]
            p_shard = jax.tree_util.tree_unflatten(td, flat_q)

        if shape.kind == "prefill":
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            c_shard = model.cache_shardings(shape.global_batch, shape.seq_len)
            bs = batch_shardings(model, batch_abs)

            def prefill_step(params, cache, batch):
                return model.prefill(params, batch["tokens"], cache=cache,
                                     frames=batch.get("frames"))
            jitted = jax.jit(prefill_step,
                             in_shardings=(p_shard, c_shard, bs),
                             out_shardings=(c_shard, None),
                             donate_argnums=(1,))
            args = (p_abs, cache_abs, batch_abs)
        else:  # decode
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            c_shard = model.cache_shardings(shape.global_batch, shape.seq_len)
            bs = batch_shardings(model, batch_abs)

            def decode_step(params, cache, batch):
                return model.decode_step(params, cache, batch["tokens"])
            jitted = jax.jit(decode_step,
                             in_shardings=(p_shard, c_shard, bs),
                             out_shardings=(c_shard, None),
                             donate_argnums=(1,))
            args = (p_abs, cache_abs, batch_abs)

    lowered = jitted.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    # --- memory -------------------------------------------------------------
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "peak_memory_in_bytes") if hasattr(ma, k)}
        # donated (aliased) args don't double-count
        live = (mem.get("argument_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0)
                - mem.get("alias_size_in_bytes", 0)
                + mem.get("temp_size_in_bytes", 0))
        mem["live_bytes_per_device"] = live
        rec["memory"] = mem
        if verbose:
            print(f"[memory/device] args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"out={mem.get('output_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"peak={mem.get('peak_memory_in_bytes', 0)/2**30:.2f}GiB "
                  f"live={live/2**30:.2f}GiB")
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    # --- cost: trip-count-aware HLO walk (hlo_parser) -------------------------
    # XLA's own cost_analysis counts scan bodies ONCE (verified); kept only
    # as a reference field. The roofline uses analyze_hlo.
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        rec["xla_cost_reference"] = {
            "flops_per_device_body_once": float(ca.get("flops", 0.0)),
            "bytes_per_device_body_once": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:  # pragma: no cover
        rec["xla_cost_reference"] = {"error": str(e)}

    txt = compiled.as_text()
    cost = hlo_parser.analyze_hlo(txt)
    flops = cost.flops_per_device
    bytes_ = cost.bytes_per_device
    rec["cost"] = {"flops_per_device": flops, "bytes_per_device": bytes_}
    rec["collectives"] = {"wire_bytes_per_device": cost.wire_bytes_per_device,
                          "by_kind": cost.wire_by_kind,
                          "n_ops": cost.n_collectives,
                          "loops": cost.loops[:32]}
    if verbose:
        print("[hlo cost/device]")
        print(cost.summary())

    # --- roofline -----------------------------------------------------------
    mf = analytic_model_flops(cfg, shape)
    rec["model_flops"] = mf
    rec["params"] = cfg.param_count()
    rec["active_params"] = cfg.active_param_count()
    rec["n_devices"] = n_dev
    rec["roofline"] = hlo_analysis.roofline_terms(
        flops, bytes_, cost.wire_bytes_per_device, mf, n_dev)
    rec["run_config"] = {"microbatch": rcfg.microbatch,
                         "sequence_parallel": rcfg.sequence_parallel,
                         "opt_state_quant": rcfg.opt_state_quant,
                         "remat": rcfg.remat}
    rec["ok"] = True
    if verbose:
        r = rec["roofline"]
        print(f"[roofline] compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"bound={r['bound']} mfu_bound={r['mfu_bound']:.3f} "
              f"useful_flops_ratio={r['useful_flops_ratio']:.3f}")
    return rec


def _save(rec: Dict[str, Any], out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = _cell_path(out_dir, rec["arch"], rec["shape"], rec["mesh"],
                      rec["quant"])
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    from repro.core.policy import add_policy_arg
    add_policy_arg(ap, default="auto",
                   extra_help="'auto' = bf16 train / pofx8 serve")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        from repro.configs import ARCHS
        from repro.configs.base import SHAPES
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        failures = []
        for arch in ARCHS:
            for shape in SHAPES:
                for mk in meshes:
                    quant = args.quant
                    if quant == "auto":
                        quant = "bf16" if SHAPES[shape].kind == "train" else "pofx8"
                    path = _cell_path(args.out, arch, shape, mk, quant)
                    if os.path.exists(path) and not args.force:
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk,
                           "--quant", args.quant, "--out", args.out]
                    print(f"=== {arch} x {shape} x {mk} ({args.quant})",
                          flush=True)
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    if r.returncode != 0:
                        failures.append((arch, shape, mk))
                        print(r.stdout[-2000:])
                        print(r.stderr[-2000:])
        print(f"sweep done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape required without --all"
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    status = 0
    for mk in meshes:
        try:
            rec = run_cell(args.arch, args.shape, mk, args.quant, args.out)
        except Exception as e:
            rec = {"arch": args.arch, "shape": args.shape, "mesh": mk,
                   "quant": args.quant, "ok": False, "error": str(e),
                   "traceback": traceback.format_exc()}
            status = 1
        path = _save(rec, args.out)
        print(f"{'OK ' if rec.get('ok') else ('SKIP' if rec.get('skipped') else 'FAIL')} -> {path}")
        if not rec.get("ok") and not rec.get("skipped"):
            print(rec.get("error", ""))
    return status


if __name__ == "__main__":
    sys.exit(main())
