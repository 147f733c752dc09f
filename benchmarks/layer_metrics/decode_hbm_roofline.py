"""Kernels (decode read path): bytes one decode step must read (weights as
stored, float32, plus K and V of the live tokens, bfloat16; from shapes and
the lanes' lengths at each traced step) over the HBM peak, over the decode
program's device time per step in the trace. The numerator is of the work,
not of the implementation: it stays true when the read path changes."""

from benchmarks.harness import flops, trace
from benchmarks.harness.peaks import peaks_for

NEEDLE = "decode_fn"


def read(run: dict):
    tr = run.get("trace")
    if not tr or "trace_window" not in run:
        return None
    runs = trace.module_runs(tr["trace"].devices[0], NEEDLE)
    t0, t1 = run["trace_window"]
    steps = [s for s in run.get("steps", ()) if t0 <= s[0] < t1 and s[1] > 0]
    if not runs or not steps:
        return None
    live = sum(s[3] for s in steps) / len(steps)
    least = flops.gpt2_decode_bytes(run["dims"], live) / peaks_for(
        run["device_kind"]
    )["hbm_bytes_per_s"]
    per_step = sum(e - s for s, e in runs) / len(runs)
    return 100.0 * least / per_step
