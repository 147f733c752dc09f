"""Kernels (ops/flash_attention.py, backward): two thirds of the least time
of one step's attention work over the device time per step of the custom
calls named ``flash_bwd`` (``flash_bwd_dq`` and ``flash_bwd_dkv``). Nothing
to read where the trace names no such call."""

from benchmarks.harness.layer_helpers import flash_share


def read(run: dict):
    return flash_share(run, "flash_bwd", 2.0 / 3.0)
