"""Kernel ``kv_flash_paged_decode`` (``kernels/kv_flash_paged_decode.py``):
share of its roofline, live context only."""
from bench.roofline_common import roofline
from bench.work.kv_flash_paged_decode import decode_step_work


def read(r):
    return roofline(r, "kv_flash_paged_decode", decode_step_work)
