"""The one traffic generator: a mix file of parameters in, a list of
requests with due times out. Every mix under ``bench/traffic/`` is read by
this code; a new mix is a new data file, not new code.

Mix file keys (``bench/traffic/<mix>.json``):

- ``arrivals``: ``{"process": "backlog", "per_slot": n}`` puts ``n`` times
  the engine's slots all due at time 0 (an offline batch; slots refill as
  requests finish), or ``{"process": "gamma", "rate_per_s": r, "cv": c,
  "initial_backlog": b}``: open-loop arrivals at mean rate ``r`` whose gaps
  have coefficient of variation ``c`` (``c = 1`` is Poisson, ``c > 1`` is
  bursty), after ``b`` requests due at time 0 that load the engine.
- ``prompt``: ``{"unique": <lengths>, "shared": {"count": k, "tokens": t}}``;
  with ``shared`` each prompt starts with one of ``k`` fixed system prompts
  of ``t`` tokens, followed by its own ``unique`` part.
- ``output``: ``<lengths>``, the tokens each request asks for.
- ``<lengths>``: ``{"median": m, "sigma": s, "min": lo, "max": hi}``, a
  lognormal clipped to ``[lo, hi]``.
- ``block``: lengths and gaps are not drawn at random. Each block of this
  many consecutive requests takes the same set of values, the quantiles
  ``(i + 0.5) / block`` of the distribution, in a shuffled order.
- ``schedule_seed``: that order, and the choice of system prompt, come from
  this number and not from the run's seed. Every run replays one schedule
  and the run's seed changes only the token ids (and the weights), so runs
  with different seeds ask for the same work in the same order.
- ``lead_in_s``: seconds the traffic runs before the measured window opens.
- ``horizon_s``: seconds of open-loop arrivals to generate past time 0.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float            # seconds after the traffic starts
    prompt: np.ndarray      # int32 token ids
    max_new: int
    shared_id: int = -1     # which system prompt, or -1


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles ``(i + 0.5) / n`` of the clipped lognormal."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def gamma_gap_quantiles(rate: float, cv: float, n: int) -> np.ndarray:
    """``n`` gamma quantiles with coefficient of variation ``cv``, scaled so
    that their mean is exactly ``1 / rate``."""
    from scipy.stats import gamma
    shape = 1.0 / (cv * cv)
    q = gamma.ppf((np.arange(n) + 0.5) / n, shape)
    return q * (1.0 / rate) / q.mean()


def _blocked(values_for_block, n: int, block: int,
             rng: np.random.Generator) -> np.ndarray:
    out = []
    while len(out) < n:
        vals = np.array(values_for_block(block))
        out.extend(vals[rng.permutation(block)])
    return np.array(out[:n])


def n_requests(mix: dict, n_slots: int) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        return int(arr["per_slot"]) * n_slots
    if arr["process"] == "gamma":
        return int(arr.get("initial_backlog", 0)) + int(
            math.ceil(arr["rate_per_s"] * mix["horizon_s"]))
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def generate(mix: dict, seed: int, vocab: int,
             n_slots: int) -> List[Arrival]:
    """The requests of one run, sorted by due time."""
    rng = np.random.default_rng(seed)
    sched = np.random.default_rng(mix["schedule_seed"])
    arr = mix["arrivals"]
    n = n_requests(mix, n_slots)
    block = int(mix["block"])
    uniq = _blocked(lambda b: lognormal_quantiles(mix["prompt"]["unique"], b),
                    n, block, sched)
    outs = _blocked(lambda b: lognormal_quantiles(mix["output"], b),
                    n, block, sched)
    due = np.zeros(n)
    if arr["process"] == "gamma":
        b0 = int(arr.get("initial_backlog", 0))
        gaps = _blocked(lambda b: gamma_gap_quantiles(
            arr["rate_per_s"], arr["cv"], b), n - b0, block, sched)
        due[b0:] = np.cumsum(gaps)
    shared = mix["prompt"].get("shared")
    systems = []
    if shared:
        systems = [rng.integers(0, vocab, shared["tokens"], dtype=np.int32)
                   for _ in range(shared["count"])]
    sids = sched.integers(0, len(systems), n) if systems else None
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, int(uniq[i]), dtype=np.int32)
        sid = -1
        if systems:
            sid = int(sids[i])
            body = np.concatenate([systems[sid], body])
        out.append(Arrival(rid=i, due_s=float(due[i]), prompt=body,
                           max_new=int(outs[i]), shared_id=sid))
    return out


def prompt_lengths(mix: dict) -> tuple:
    """(shortest, longest) prompt the mix can produce."""
    u = mix["prompt"]["unique"]
    base = mix["prompt"].get("shared", {}).get("tokens", 0)
    return base + int(u["min"]), base + int(u["max"])
