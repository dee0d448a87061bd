"""Kernel ``pofx_matmul`` (``kernels/pofx_matmul.py``) in the decode
program: share of its roofline, live rows only."""
from bench.roofline_common import roofline
from bench.work.pofx_matmul import decode_step_work


def read(r):
    return roofline(r, "pofx_matmul",
                    lambda cfg, ls: decode_step_work(cfg, len(ls)))
