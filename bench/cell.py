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
    return Cell(
        name=name, config_name=w["config"],
        config=load_config(w["config"], bench, root),
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


def model_config(config: dict):
    """The system's ``ModelConfig`` for a configuration file."""
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
