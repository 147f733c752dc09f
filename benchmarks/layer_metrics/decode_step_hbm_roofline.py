"""Kernels (the decode program): bytes one decode step must read, by the
configuration's own count (``decode_step_bytes(dims, live_tokens, rows)``
of the reference its file names: every stored weight once, plus the cached
state of the live tokens at each traced step), over the HBM peak, over the
decode program's device time per step in the trace — as
``decode_hbm_roofline``, whose count is GPT-2's. Of the work, not of the
implementation: it stays true when the read path changes. None where the
run has no trace or the reference no such count."""

from benchmarks.harness import common, trace
from benchmarks.harness.peaks import peaks_for

NEEDLE = "decode_fn"


def read(run: dict):
    tr = run.get("trace")
    if not tr or "trace_window" not in run:
        return None
    ref = common.load_by_path("references", run["cell"].config["reference"])
    if not hasattr(ref, "decode_step_bytes"):
        return None
    runs = trace.module_runs(tr["trace"].devices[0], NEEDLE)
    t0, t1 = run["trace_window"]
    steps = [s for s in run.get("steps", ()) if t0 <= s[0] < t1 and s[1] > 0]
    if not runs or not steps:
        return None
    live = sum(s[3] for s in steps) / len(steps)
    least = ref.decode_step_bytes(run["dims"], live, run["slots"]) / peaks_for(
        run["device_kind"]
    )["hbm_bytes_per_s"]
    per_step = sum(e - s for s, e in runs) / len(runs)
    return 100.0 * least / per_step
