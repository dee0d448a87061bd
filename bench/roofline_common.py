"""A kernel's share of its roofline in the traced chunks: the least time
the chip could take for the work the calls needed (the larger of FLOPs
over the bf16 peak and bytes over HBM bandwidth), over the summed device
time of the kernel's events in the decode program."""
from __future__ import annotations

from bench.device import log


def roofline(r, kernel: str, step_work) -> float | None:
    if r.trace is None:
        return None
    secs = r.trace.kernel_s.get(kernel)
    steps = [ls for c in r.traced_calls if c.kind == "step"
             for ls in c.lengths]
    if not secs or not steps:
        return None
    flops = nbytes = 0.0
    for ls in steps:
        f, b = step_work(r.config, ls)
        flops, nbytes = flops + f, nbytes + b
    t_flops = flops / r.peaks.bf16_flops
    t_bytes = nbytes / r.peaks.hbm_bytes_per_s
    bound = "memory" if t_bytes >= t_flops else "compute"
    log(f"roofline {kernel}: {flops:.6g} FLOP, {nbytes:.6g} B over "
        f"{len(steps)} decode steps in {secs:.6f} s of kernel time; "
        f"{bound}-bound")
    return 100.0 * max(t_flops, t_bytes) / secs
