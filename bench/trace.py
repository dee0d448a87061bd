"""From a profiler trace to device numbers.

``compact`` keeps, from the ``.xplane.pb`` file the profiler writes, the
device operations (name, start, duration, the kernel it belongs to) and
the benchmark's own host spans (``bench.*``), all in
nanoseconds on one clock. ``summarize`` reduces that to the traced window,
the busy time (the union of the device-op intervals), the device time of
each kernel, the operations that took most time and the longest idle gaps,
each labelled by the host span open during it. The compact form is what
``bench/testdata`` records, so the reduction is tested without a chip.

Kernels are told apart by the jitted wrapper each is called through, which
names the HLO instruction the trace shows (every Pallas body is called
``_kernel``): ``%pofx_matmul.69``, ``%kv_flash_decode.3``,
``%kv_flash_paged_decode.8``. A kernel's time counts only its ops inside a
decode chunk: the host blocks on each chunk's tokens inside its
``bench.step`` span, so the chunk's device work lies within that span.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

KERNELS = ("kv_flash_paged_decode", "kv_flash_decode", "pofx_matmul")
DECODE_SPAN = "bench.step"       # the host span around each decode chunk
WINDOW_SPAN = "bench.traced"     # the host span around the traced window


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def kernel_of(text: str) -> Optional[str]:
    for k in KERNELS:
        if k in text:
            return k
    return None


def compact(path) -> dict:
    """{"device": [[op, start_ns, dur_ns, kernel], ...], "host": [[span,
    start_ns, dur_ns], ...]} of one trace file. Device ops are the "XLA Ops"
    line of each TPU plane; an op is named by its HLO instruction, which for
    a Pallas call is the jitted wrapper's name (``%pofx_matmul.69 = ...``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = ev.name.split(" = ")[0]
                    device.append([name, float(ev.start_ns),
                                   float(ev.duration_ns), kernel_of(name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": device, "host": host}


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"busy_s": self.busy_s, "window_s": self.window_s,
                "device_ops": [list(x) for x in self.top_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_label(name: str, kernel: Optional[str]) -> str:
    if kernel:
        return kernel
    base = name.lstrip("%").rsplit(".", 1)[0]
    return base or name


def _leaves(ops):
    """Drop ops that contain another op (a ``while`` around its body): their
    time is their children's, and counting both would count it twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    container, stack = set(), []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            container.add(stack[-1])
        stack.append(i)
    return [op for i, op in enumerate(ops) if i not in container]


def summarize(c: dict, max_gaps: Optional[int] = 10) -> Summary:
    """Reduce a compact trace over the host span ``WINDOW_SPAN``; keep the
    ``max_gaps`` longest idle gaps (all with None). A kernel's time counts
    its ops that start inside a decode chunk's host span."""
    spans = [h for h in c["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = spans[0][1], spans[0][1] + spans[0][2]
    ops = _leaves([(max(s, lo), min(s + d, hi), n, k)
                   for n, s, d, k in (ev[:4] for ev in c["device"])
                   if s < hi and s + d > lo])
    busy = _merge([(s, e) for s, e, *_ in ops])
    busy_ns = sum(e - s for s, e in busy)
    chunks = [(h[1], h[1] + h[2]) for h in c["host"] if h[0] == DECODE_SPAN]
    kernel_ns: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    for s, e, n, k in ops:
        label = _op_label(n, k)
        by_op[label] = by_op.get(label, 0.0) + (e - s)
        if k and any(a <= s < b for a, b in chunks):
            kernel_ns[k] = kernel_ns.get(k, 0.0) + (e - s)
    gaps = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    inner = [h for h in c["host"] if h[0] != WINDOW_SPAN]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= 0:
            continue
        mid = (a + b) / 2
        open_ = [h for h in inner if h[1] <= mid <= h[1] + h[2]]
        label = min(open_, key=lambda h: h[2])[0] if open_ else "no span"
        gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
                   kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
                   top_ops=[(n, v / 1e9) for n, v in top],
                   idle_gaps=gaps if max_gaps is None else gaps[:max_gaps])
