"""The reduction from a trace to numbers: on synthetic events, and on a
small ``.xplane.pb`` recorded on the chip (``data/*.xplane.pb``)."""

import glob
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _dev():
    ops = [
        ("while", 0.0, 10.0), ("fusion.1", 0.0, 4.0), ("attn.3", 4.0, 9.0),
        ("copy.2", 12.0, 13.0), ("attn.4", 13.0, 13.5),
    ]
    mods = [("jit_step_fn(1)", 0.0, 10.0), ("jit__decode_fn(2)", 12.0, 13.5)]
    return trace.DeviceTrace(ops, mods, {"attn.3", "attn.4"})


def test_union_and_busy():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.busy_seconds(_dev()) == pytest.approx(11.5)


def test_self_time_of_nested_ops():
    got = trace.op_seconds(_dev())
    assert got["while"] == pytest.approx(1.0)  # 10 less its children's 9
    assert got["fusion.1"] == pytest.approx(4.0)
    assert got["attn.3"] == pytest.approx(5.0)
    assert sum(got.values()) == pytest.approx(11.5)


def test_seconds_matching_and_module_runs():
    total, count = trace.seconds_matching(_dev(), ("attn",))
    assert (total, count) == (pytest.approx(5.5), 2)
    assert trace.module_runs(_dev(), "decode_fn") == [(12.0, 13.5)]


def test_names():
    text = "%attn.135 = (bf16[128,1024,64]{2,1,0}) custom-call(s32[128] %x)"
    assert trace.short_name(text) == "attn.135"
    assert trace.family("attn.135") == "attn"
    assert trace.family("while") == "while"
    assert trace.family("fusion.clone.2") == "fusion.clone"
    total, count = trace.seconds_matching(_dev(), ("attn", "copy"), custom_only=True)
    assert (total, count) == (pytest.approx(5.5), 2)


def test_gaps_go_to_the_innermost_host_span():
    gaps = trace.idle_gaps(_dev(), 0.0, 15.0)
    assert gaps == [(10.0, 12.0), (13.5, 15.0)]
    spans = [("step", 9.0, 14.0, 0), ("schedule", 10.5, 11.0, 1)]
    by = trace.attribute_gaps(gaps, spans)
    assert by["schedule"] == pytest.approx(0.5)
    assert by["step"] == pytest.approx(1.5 + 0.5)
    assert by["host_other"] == pytest.approx(1.0)


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb")))
    or [None],
)
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace committed")
    tr = trace.load(path)
    assert tr.devices and tr.devices[0].ops
    out = trace.reduce_trace(tr, window_s=1e9)
    assert 0 < out["busy_s"]
    assert len(out["device_ops"]) <= 10 and out["device_ops"][0][1] > 0
    busy_by_ops = sum(trace.op_seconds(tr.devices[0]).values())
    assert busy_by_ops == pytest.approx(out["busy_s"], rel=1e-6)
