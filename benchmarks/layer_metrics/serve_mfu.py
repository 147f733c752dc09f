"""Serving executables: model FLOPs of every prompt admitted and every
output token decoded in the window, from shapes (a prefill computes the
head at one position), over the window's seconds and the chip's bf16
peak."""

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for


def read(run: dict):
    if "prefill_lens" not in run or not run.get("window_s"):
        return None
    d = run["dims"]
    total = flops.gpt2_forward_flops(
        d, run["decode_tokens"], run["decode_ctx"], run["decode_tokens"]
    )
    for plen in run["prefill_lens"]:
        total += flops.gpt2_forward_flops(
            d, plen, flops.causal_ctx_sum(plen), 1
        )
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * total / run["window_s"] / run["chips"] / peak
