"""Kernels (ops/flash_attention.py): the least time one step's attention
work could take (forward and backward, causal; FLOPs against the bf16 peak
and bytes against HBM, whichever is larger: at head size 64 and 1024
positions the two are close, see PERF.md) over the device time of the flash
custom calls per step in the trace. Nothing to read where the trace names
no flash call."""

from benchmarks.harness import flops, trace
from benchmarks.harness.peaks import peaks_for

# The program gives its kernels no name of their own: in the trace a flash
# call is a Pallas custom call named after the flax scope that made it,
# ``attn.<n>`` (PERF.md lists the missing ``name=`` for the tracing issue).
NEEDLES = ("attn", "flash")


def read(run: dict):
    tr = run.get("trace")
    if not tr or not run.get("trace_steps"):
        return None
    seconds, count = trace.seconds_matching(
        tr["trace"].devices[0], NEEDLES, custom_only=True
    )
    if not count:
        return None
    work = flops.attention_train_work(
        run["dims"], int(run["cell"].traffic["batch_per_chip"]),
        int(run["cell"].traffic["seq_len"]),
    )
    least = flops.roofline_seconds(
        work["flops"], work["bytes"], peaks_for(run["device_kind"])
    )
    return 100.0 * least["seconds"] / (seconds / run["trace_steps"])
