"""Scheduler: milliseconds the chip sat idle inside one ``engine.step``
(the engine's own ``engine_step`` span), mean over the steps of the traced
window that ran a decode: the idle time the engine owns, and not the
harness's loop around it."""

from benchmarks.harness.layer_helpers import idle_ms_per_span, traced_spans


def read(run: dict):
    decodes = traced_spans(run, "decode")
    steps = [
        (s, e) for s, e in traced_spans(run, "engine_step")
        if any(s <= d0 and d1 <= e for d0, d1 in decodes)
    ]
    return idle_ms_per_span(run, steps)
