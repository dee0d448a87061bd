"""Production mesh construction (functions only — importing this module
never touches jax device state).

Single pod: 256 chips as (16, 16) ("data", "model").
Multi pod:  2 pods x 256 chips as (2, 16, 16) ("pod", "data", "model");
the "pod" axis crosses DCN — gradient all-reduce (optionally posit8-
compressed, runtime/compression.py) is the only traffic on it.
Serving:    a 1-D ("tp",) mesh for the tensor-parallel engine
(DESIGN.md §9); CPU CI fakes devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_tp_mesh"]


def _make_mesh(shape, axes, devices=None):
    # jax.make_mesh defaults to Explicit axes; the model code shards through
    # with_sharding_constraint and shard_map, which need Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1,
                   devices=None):
    """Small mesh over whatever devices exist (tests / examples)."""
    shape = (pod, data, model) if pod > 1 else (data, model)
    axes = ("pod", "data", "model") if pod > 1 else ("data", "model")
    return _make_mesh(shape, axes, devices)


def make_tp_mesh(tp: int, devices=None):
    """1-D ("tp",) serving mesh for the tensor-parallel engine.

    Uses the first ``tp`` local devices when ``devices`` is not given, so
    a tp smaller than the device count works (the differential tests run
    tp in {1, 2, 4} against one forced-4-device process).
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if devices is None:
        avail = jax.devices()
        if len(avail) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices but only {len(avail)} exist "
                "(CPU: set XLA_FLAGS=--xla_force_host_platform_device_count)")
        devices = avail[:tp]
    return _make_mesh((tp,), ("tp",), devices)
