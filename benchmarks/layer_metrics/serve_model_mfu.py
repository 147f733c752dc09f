"""Serving executables: model FLOPs of every prompt admitted and every
output token decoded in the window, over the window's seconds and the
chip's bf16 peak — as ``serve_mfu``, but the FLOPs are the
configuration's own: ``forward_flops(dims, n_tokens, ctx_sum, head_rows)``
of the reference its file names (a prefill computes the head at one
position). None where the reference has no such count."""

from benchmarks.harness import common, flops
from benchmarks.harness.peaks import peaks_for


def read(run: dict):
    if "prefill_lens" not in run or not run.get("window_s"):
        return None
    ref = common.load_by_path("references", run["cell"].config["reference"])
    if not hasattr(ref, "forward_flops"):
        return None
    d = run["dims"]
    total = ref.forward_flops(
        d, run["decode_tokens"], run["decode_ctx"], run["decode_tokens"]
    )
    for plen in run["prefill_lens"]:
        total += ref.forward_flops(d, plen, flops.causal_ctx_sum(plen), 1)
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * total / run["window_s"] / run["chips"] / peak
