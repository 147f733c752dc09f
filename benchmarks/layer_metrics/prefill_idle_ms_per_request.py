"""Scheduler: milliseconds the chip sat idle inside one admission (the
engine's host ``prefill`` span), mean over the admissions of the traced
window: how much of ``prefill_ms_per_request`` the chip did not work."""

from benchmarks.harness.layer_helpers import idle_ms_per_span, traced_spans


def read(run: dict):
    return idle_ms_per_span(run, traced_spans(run, "prefill"))
