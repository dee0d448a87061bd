"""A cell of BENCHMARK.json, and the files it names: the configuration
(``bench/configs/<config>.json``), the traffic mix
(``bench/traffic/<traffic>.json``) and the reader of each per-layer metric
(``bench/metrics/<metric>.py``). Everything is found by name, so a new cell
or metric is new files and a new entry, not an edit."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    chips: int
    end_to_end: List[dict]      # the metric entries this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, bench: dict, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"BENCHMARK.json has no configuration {name!r}")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    from bench.generator import load_mix
    bench = load_benchmark(root)
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = load_config(w["config"], bench, root)
    tp = config["serving"].get("tp", 1)
    if tp > int(w["chips"]):
        raise ValueError(f"workload {name!r} shards over tp = {tp} chips and "
                         f"asks for {w['chips']}")
    return Cell(
        name=name, config_name=w["config"], config=config,
        traffic_name=w["traffic"], mix=load_mix(w["traffic"]),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _int(v, config):
    return int(v)


def _float(v, config):
    return float(v)


def _bool(v, config):
    return bool(v)


def _str(v, config):
    return str(v)


def _times_hidden(v, config):
    """An inner width published as a multiple of the hidden size."""
    return int(v * config["hidden_size"])


# A configuration file's keys, each read in one of five ways.
#
# 1. Published keys (the names in a model's own config.json) that set a
#    ``ModelConfig`` field: key -> (field, conversion of (value, file)). One
#    field may have several publishers' names; a file that gives two of
#    them with different values is refused. A value of null leaves the
#    field at its default.
_FIELDS = {
    "num_hidden_layers": ("n_layers", _int),
    "hidden_size": ("d_model", _int),
    "num_attention_heads": ("n_heads", _int),
    "num_key_value_heads": ("n_kv_heads", _int),
    "head_dim": ("d_head", _int),
    "intermediate_size": ("d_ff", _int),
    "vocab_size": ("vocab_size", _int),
    "hidden_act": ("act", _str),
    "rope_theta": ("rope_theta", _float),
    "rms_norm_eps": ("norm_eps", _float),
    "tie_word_embeddings": ("tie_embeddings", _bool),
    "qk_norm": ("qk_norm", _bool),
    "use_qk_norm": ("qk_norm", _bool),
    # experts: the count held on this chip (model-configs guide, section 4)
    "num_local_experts": ("n_experts", _int),
    "n_routed_experts": ("n_experts", _int),
    "num_experts": ("n_experts", _int),
    "num_experts_per_tok": ("top_k", _int),
    "n_shared_experts": ("n_shared_experts", _int),
    "num_shared_experts": ("n_shared_experts", _int),
    # every k-th layer routes, counted from the first (layer i is an expert
    # layer when (i + 1) % k == 0), as the system groups them
    "decoder_sparse_step": ("moe_every", _int),
    "interleave_moe_layer_step": ("moe_every", _int),
    # state space
    "state_size": ("ssm_state", _int),
    "ssm_state_size": ("ssm_state", _int),
    "mamba_d_state": ("ssm_state", _int),
    "expand": ("d_inner", _times_hidden),
    "mamba_expand": ("d_inner", _times_hidden),
    "conv_kernel": ("conv_width", _int),
    "mamba_d_conv": ("conv_width", _int),
    "time_step_rank": ("dt_rank", _int),
    "mamba_dt_rank": ("dt_rank", _int),
    "mamba_head_dim": ("ssm_head_dim", _int),
    "mamba_headdim": ("ssm_head_dim", _int),
    "mamba_d_head": ("ssm_head_dim", _int),
}
# A mamba1 model (family "ssm") publishes its inner width as
# ``intermediate_size``; it has no MLP.
_FAMILY_FIELDS = {
    "ssm": {"intermediate_size": ("d_inner", _int)},
}
# 2. Published keys the system implements at one value only: the file may
#    state that value, and any other is refused.
_ONLY = {
    "attention_bias": False, "mlp_bias": False, "use_bias": False,
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
    "partial_rotary_factor": 1.0, "first_k_dense_replace": 0,
    "num_dense_layers": 0, "moe_layer_freq": 1, "mamba_n_groups": 1,
    "mamba_ngroups": 1,
}
# 3. Published keys that do not change what a served forward computes from
#    given weights: token ids, initialisation, training losses and dropout,
#    the longest context, kernel choices, and multi-token-prediction modules
#    that plain decoding does not run.
_INERT = {
    "bos_token_id", "eos_token_id", "pad_token_id", "max_position_embeddings",
    "model_max_length", "initializer_range", "torch_dtype",
    "transformers_version", "architectures", "use_cache",
    "attention_dropout", "hidden_dropout", "router_aux_loss_coef",
    "output_router_logits", "load_balance_coeff", "seq_aux",
    "time_step_floor", "time_step_min", "time_step_max",
    "rescale_prenorm_residual", "num_logits_to_keep", "use_mamba_kernels",
    "use_grouped_mm", "ep_size", "tf_legacy_loss", "num_nextn_predict_layers",
}
# 4. Keys that document the configuration or say how it is served.
#    ``"published"`` holds the published keys no ModelConfig field holds yet
#    (an expert width, a window): they are not served, and
#    ``bench/work/model.py`` counts the published model from them.
_DOC = {
    "name", "family", "source", "paper", "reduced", "published", "assumed",
    "deployment", "reference", "serving", "check", "model_type", "system",
}
# 5. ``"system"``: ModelConfig fields no published key expresses, passed as
#    they are (the change that adds a field for a key under "published"
#    sets it here).


def model_fields(config: dict) -> dict:
    """The ``ModelConfig`` fields a configuration file states, by the table
    above. A key it cannot place raises, naming the key: a configuration is
    never served as another model in silence."""
    family = config.get("family", "dense")
    table = dict(_FIELDS, **_FAMILY_FIELDS.get(family, {}))
    fields = {"name": config["name"], "family": family}
    for key, value in config.items():
        if key in _DOC or (key in _INERT and key not in table):
            continue
        if key in table:
            if value is None:
                continue
            field, convert = table[key]
            v = convert(value, config)
            if fields.get(field, v) != v:
                raise ValueError(
                    f"configuration {config['name']!r}: {key!r} = {value!r} "
                    f"sets {field} to {v!r}, another key set {fields[field]!r}")
            fields[field] = v
        elif key in _ONLY:
            if value != _ONLY[key]:
                raise ValueError(
                    f"configuration {config['name']!r}: {key!r} = {value!r}; "
                    f"the system computes only {key} = {_ONLY[key]!r}")
        else:
            raise ValueError(
                f"configuration {config['name']!r}: key {key!r} has no "
                f"ModelConfig field; a published key the system does not "
                f"serve yet goes under \"published\"")
    for field, v in config.get("system", {}).items():
        if fields.get(field, v) != v:
            raise ValueError(
                f"configuration {config['name']!r}: \"system\" sets {field} "
                f"to {v!r}, a published key set {fields[field]!r}")
        fields[field] = v
    return fields


def model_config(config: dict):
    """The system's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**model_fields(config))
