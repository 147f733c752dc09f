"""Kernels (ops/flash_attention.py, forward): a third of the least time of
one step's attention work over the device time per step of the custom calls
named ``flash_fwd``. Nothing to read where the trace names no such call."""

from benchmarks.harness.layer_helpers import flash_share


def read(run: dict):
    return flash_share(run, "flash_fwd", 1.0 / 3.0)
