"""The per-layer readers that read the program's own spans and kernel names
(PR 24), each on a hand-made record whose answer is worked out by hand, and
on a record without its spans or names (the parent's, an untraced run): None,
never 0 and never an exception."""

import types

import pytest

from benchmarks.harness import common, flops, trace
from benchmarks.harness.peaks import PEAKS

KIND = "TPU v5 lite"
DIMS = {"n_layer": 2, "n_head": 2, "n_embd": 128, "n_positions": 64,
        "vocab_size": 256}
SHIFT = 100.0  # perf_counter -> trace clock


def reader(name: str):
    return common.load_by_path("layer_metrics", name).read


def serve_record() -> dict:
    """Host clock 0..10, traced from 4 to 10. Two engine steps inside the
    traced window, the first with an admission; one before it; one that
    straddles its start; one that ran no decode."""
    on = lambda a, b: (a + SHIFT, b + SHIFT)  # noqa: E731
    ops = [
        # step A (5.0-6.0): prefill busy 5.10-5.25, decode busy 5.5-5.9
        ("fusion.1", *on(5.10, 5.25)), ("copy.2", *on(5.5, 5.9)),
        # step B (7.0-7.5): decode busy 7.1-7.45, in two ops that touch
        ("copy.3", *on(7.1, 7.3)), ("fusion.4", *on(7.3, 7.45)),
        # outside any span
        ("copy.5", *on(8.0, 8.2)),
    ]
    dev = trace.DeviceTrace(ops, [], set())
    spans = [
        ("warmup", 0.5, 2.75, 0), ("compile", 0.6, 2.7, 1),
        ("engine_step", 3.0, 3.4, 0), ("decode_prepare", 3.0, 3.004, 1),
        ("decode", 3.05, 3.3, 1),
        ("engine_step", 3.9, 4.2, 0), ("decode", 3.95, 4.1, 1),
        # A: idle 5.0-5.10, 5.25-5.5, 5.9-6.0 = 0.45
        ("engine_step", 5.0, 6.0, 0), ("schedule", 5.0, 5.01, 1),
        # its prefill 5.05-5.35: idle 5.05-5.10 and 5.25-5.35 = 0.15
        ("prefill", 5.05, 5.35, 1), ("prefill_prepare", 5.05, 5.09, 2),
        ("prefill_readback", 5.2, 5.35, 2),
        ("decode_prepare", 5.4, 5.406, 1), ("decode", 5.45, 5.92, 1),
        ("collect", 5.92, 5.99, 1),
        # B: idle 7.0-7.1 and 7.45-7.5 = 0.15
        ("engine_step", 7.0, 7.5, 0), ("decode_prepare", 7.0, 7.002, 1),
        ("decode", 7.05, 7.46, 1),
        # no decode: not a step the metric counts
        ("engine_step", 9.0, 9.1, 0), ("schedule", 9.0, 9.01, 1),
    ]
    return {
        "spans": spans, "window": (2.9, 10.0), "trace_window": (4.0, 10.0),
        "trace": {"trace": trace.Trace([dev], SHIFT + 4.0),
                  "clock_shift_s": SHIFT, "busy_s": 1.1, "window_s": 6.0},
    }


def train_record() -> dict:
    cell = types.SimpleNamespace(
        traffic={"batch_per_chip": 2, "seq_len": 64}
    )
    ops = [
        ("fusion.9", 0.0, 0.5),
        ("flash_fwd.1", 1.0, 1.002), ("flash_fwd.2", 2.0, 2.002),
        ("flash_bwd_dq.3", 3.0, 3.004), ("flash_bwd_dkv.4", 3.5, 3.508),
        ("flash_bwd_dq.5", 4.0, 4.004), ("flash_bwd_dkv.6", 4.5, 4.508),
        ("fused_adamw.7", 5.0, 5.1),
        ("flash_fwd_shaped_fusion.8", 6.0, 7.0),  # no custom call: not read
    ]
    custom = {n for n, _, _ in ops if n.split(".")[0] in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "fused_adamw"
    )}
    dev = trace.DeviceTrace(ops, [], custom)
    return {
        "cell": cell, "dims": DIMS, "device_kind": KIND, "trace_steps": 2,
        "trace": {"trace": trace.Trace([dev], None)}, "spans": [],
    }


def test_engine_idle_ms_per_step():
    # steps A and B alone: (0.45 + 0.15) / 2 seconds
    assert reader("engine_idle_ms_per_step")(serve_record()) == (
        pytest.approx(300.0)
    )


def test_prefill_idle_ms_per_request():
    assert reader("prefill_idle_ms_per_request")(serve_record()) == (
        pytest.approx(150.0)
    )


def test_decode_prepare_ms_per_step():
    # every decode_prepare of the window (from 2.9): 4, 6 and 2 ms
    assert reader("decode_prepare_ms_per_step")(serve_record()) == (
        pytest.approx(4.0)
    )


def test_engine_warmup_s():
    assert reader("engine_warmup_s")(serve_record()) == pytest.approx(2.25)


def test_flash_rooflines_split_the_work_in_thirds():
    run = train_record()
    work = flops.attention_train_work(DIMS, 2, 64)
    least = flops.roofline_seconds(work["flops"], work["bytes"], PEAKS[KIND])
    # per step: forward 2 x 2 ms / 2 steps, backward 2 x (4 + 8) ms / 2
    fwd = reader("flash_fwd_roofline")(run)
    bwd = reader("flash_bwd_roofline")(run)
    assert fwd == pytest.approx(100.0 * least["seconds"] / 3 / 0.002)
    assert bwd == pytest.approx(100.0 * least["seconds"] * 2 / 3 / 0.012)
    # together they are what flash_attn_roofline reads as one
    whole = reader("flash_attn_roofline")(run)
    assert whole == pytest.approx(100.0 * least["seconds"] / 0.014)
    assert 1 / whole == pytest.approx(1 / (3 * fwd) + 2 / (3 * bwd))


@pytest.mark.parametrize("name", [
    "engine_idle_ms_per_step", "prefill_idle_ms_per_request",
    "decode_prepare_ms_per_step", "engine_warmup_s",
])
def test_serving_readers_find_nothing_on_the_parents_record(name):
    run = serve_record()
    run["spans"] = [
        s for s in run["spans"] if s[0] in ("schedule", "decode")
    ] + ([("prefill", 5.05, 5.35, 0)] if "prefill" not in name else [])
    assert reader(name)(run) is None
    untraced = {"spans": run["spans"], "window": (2.9, 10.0), "trace": None}
    assert reader(name)(untraced) is None
    assert reader(name)({}) is None


def test_span_idle_readers_need_the_clock_shift():
    run = serve_record()
    del run["trace"]["clock_shift_s"]  # a trace without the anchor
    assert reader("engine_idle_ms_per_step")(run) is None
    assert reader("prefill_idle_ms_per_request")(run) is None


@pytest.mark.parametrize("name", ["flash_fwd_roofline", "flash_bwd_roofline"])
def test_flash_readers_find_nothing_without_their_names(name):
    run = train_record()
    dev = run["trace"]["trace"].devices[0]
    # the parent's trace: every flash call is named after its flax scope
    dev.ops = [("attn." + n.split(".")[1], s, e) for n, s, e in dev.ops]
    dev.custom_calls = {n for n, _, _ in dev.ops}
    assert reader(name)(run) is None
    assert reader("flash_attn_roofline")(run) is not None
    assert reader(name)({**run, "trace": None}) is None
    assert reader(name)({}) is None
