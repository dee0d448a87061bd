"""Kernel ``kv_flash_decode`` (``kernels/kv_flash_decode.py``): share of its
roofline, live context only."""
from bench.roofline_common import roofline
from bench.work.kv_flash_decode import decode_step_work


def read(r):
    return roofline(r, "kv_flash_decode", decode_step_work)
