"""The chip a run stands on: refuse anything but an accelerator the peaks
table knows, and count compilations."""
from __future__ import annotations

import sys

from bench.peaks import peaks_for


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int, devices=None):
    """(devices, peaks) when JAX sees at least ``n`` TPU chips of a kind the
    peaks table knows; raises ``NoChip`` or ``ValueError`` otherwise."""
    if devices is None:
        import jax
        devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are on {platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices, peaks_for(devices[0].device_kind)


class CompileClock:
    """Backend compilations JAX reports through its monitoring events:
    how many, and the seconds they took."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
