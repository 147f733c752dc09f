"""What several per-layer readers share: the chip's idle time inside the
program's own spans, and one flash kernel's share of its roofline. Both read
the driver's record as it is and return None where the record lacks what
they read (a program without these spans or kernel names, an untraced run).
"""

from __future__ import annotations

from benchmarks.harness import flops, trace
from benchmarks.harness.peaks import peaks_for


def traced_spans(run: dict, name: str) -> list[tuple[float, float]]:
    """(start, end) on ``time.perf_counter`` of the ``name`` spans that lie
    wholly inside the traced window."""
    if "trace_window" not in run:
        return []
    t0, t1 = run["trace_window"]
    return [
        (s, e) for n, s, e, _ in run.get("spans", ())
        if n == name and t0 <= s and e <= t1
    ]


def idle_ms_per_span(run: dict, spans) -> float | None:
    """Milliseconds device 0 ran nothing inside each of ``spans`` (host
    clock; shifted onto the trace's by ``clock_shift_s``), mean per span."""
    tr = run.get("trace")
    if not tr or "clock_shift_s" not in tr or not spans:
        return None
    shift, dev = tr["clock_shift_s"], tr["trace"].devices[0]
    # merge the ops once, not once a span: idle_gaps merges what it is given
    busy = trace.DeviceTrace(
        [("", a, b) for a, b in trace.union((s, e) for _, s, e in dev.ops)],
        [],
    )
    idle = sum(
        b - a for s, e in spans
        for a, b in trace.idle_gaps(busy, s + shift, e + shift)
    )
    return 1e3 * idle / len(spans)


def flash_share(run: dict, needle: str, part: float) -> float | None:
    """Percent of its roofline reached by the flash custom calls whose name
    holds ``needle``, which do ``part`` of ``flops.attention_train_work``
    (its docstring: of 6 matmuls and 12 tensor passes the forward does 2
    and 4, the backward 4 and 8)."""
    tr = run.get("trace")
    if not tr or not run.get("trace_steps"):
        return None
    seconds, count = trace.seconds_matching(
        tr["trace"].devices[0], (needle,), custom_only=True
    )
    if not count:
        return None
    work = flops.attention_train_work(
        run["dims"], int(run["cell"].traffic["batch_per_chip"]),
        int(run["cell"].traffic["seq_len"]),
    )
    least = flops.roofline_seconds(
        part * work["flops"], part * work["bytes"],
        peaks_for(run["device_kind"]),
    )
    return 100.0 * least["seconds"] / (seconds / run["trace_steps"])
