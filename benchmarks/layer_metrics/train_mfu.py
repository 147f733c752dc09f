"""Step program: model FLOPs per sample from shapes (the configuration's
reference names its family's count in ``harness/flops.py``: forward and
backward, causal attention counted once, nothing recomputed) times the measured
samples per second per chip, over the chip's bf16 peak."""

from benchmarks.harness.peaks import peaks_for


def read(run: dict):
    if not run.get("samples"):
        return None
    per_sample = run["reference"].train_flops_per_sample(
        run["dims"], run["cell"].traffic
    )
    rate = run["samples"] / run["window_s"] / run["chips"]
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_sample * rate / peak
