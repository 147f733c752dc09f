"""The GLM-4.7-Flash family through the harness, at a test's size on the
CPU (``data/glm_tiny.json`` over ``data/closed_tiny.json``): a sound run is
correct, the control and the planted fault are not, through the harness's
own comparison and result line; and the two per-layer readers this
configuration brings, each on a hand-made record whose answer is worked out
by hand, and on a record with nothing for them to read: None.

The fixture's limit, from readings on this size (3 seeds, CPU): sound runs
0.0020-0.0039, the fp8 control 0.044-0.051, an altered token 0.94-1.00;
0.012 is three times the first and under a third of the second.
"""

import io
import json
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import common, faults, flops, trace
from benchmarks.harness.peaks import PEAKS

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 11
KIND = "TPU v5 lite"


def _cell():
    return common.make_cell(
        "glm_tiny", 1, os.path.join(HERE, "data", "glm_tiny.json"),
        os.path.join(HERE, "data", "closed_tiny.json"),
        end_to_end=[], per_layer=[],
    )


def _drive(hooks, monkeypatch):
    monkeypatch.setattr(
        common, "units_of",
        lambda: {k: "x" for k in ("setup_s", "serve_tokens_per_s")},
    )
    buf = io.StringIO()
    ok = bench_run.run_cell(_cell(), SEED, 2.0, False, hooks=hooks, out=buf)
    line = json.loads(buf.getvalue().splitlines()[-1])
    assert line["correct"] == ok
    return line


def test_a_sound_run_is_correct(monkeypatch):
    line = _drive(None, monkeypatch)
    assert line["correct"], line["checked"]
    assert line["checked_tokens"] > 100


@pytest.mark.parametrize("plant", ["control", "altered_token"])
def test_the_control_and_the_fault_come_out_not_correct(plant, monkeypatch):
    line = _drive(faults.hooks(plant), monkeypatch)
    assert not line["correct"], line["checked"]
    gap = line["checked"]["logit_gap"]
    assert gap["value"] > gap["limit"]


# -- the readers -----------------------------------------------------------


def reader(name: str):
    return common.load_by_path("layer_metrics", name).read


def _record() -> dict:
    cell = _cell()
    ref = common.load_by_path("references", cell.config["reference"])
    dev = trace.DeviceTrace(
        [("fusion.1", 5.0, 5.5)],
        # two runs of the decode program, 10 ms and 30 ms; a prefill's
        [("jit__decode_fn(123)", 5.0, 5.01), ("jit__decode_fn(123)", 6.0, 6.03),
         ("jit__prefill_fn(7)", 7.0, 7.5)],
        set(),
    )
    return {
        "cell": cell, "dims": ref.dims(cell.config), "device_kind": KIND,
        "window_s": 2.0, "chips": 1, "slots": 4,
        "prefill_lens": [10, 20], "decode_tokens": 100, "decode_ctx": 3000,
        # (t, active lanes, used share, live tokens): two steps inside the
        # traced window with lanes running, one with none, one before it
        "steps": [(3.0, 4, 0.5, 999), (5.0, 4, 0.5, 100), (6.0, 2, 0.5, 60),
                  (7.0, 0, 0.0, 0)],
        "trace_window": (4.0, 10.0),
        "trace": {"trace": trace.Trace([dev], None)},
    }


def test_serve_model_mfu_counts_the_configurations_own_flops():
    run = _record()
    ref = common.load_by_path("references", "glm4_moe_lite")
    d = run["dims"]
    total = (
        ref.forward_flops(d, 100, 3000, 100)
        + ref.forward_flops(d, 10, flops.causal_ctx_sum(10), 1)
        + ref.forward_flops(d, 20, flops.causal_ctx_sum(20), 1)
    )
    peak = PEAKS[KIND]["bf16_flops_per_s"]
    assert reader("serve_model_mfu")(run) == pytest.approx(
        100.0 * total / 2.0 / peak
    )


def test_forward_flops_by_hand():
    ref = common.load_by_path("references", "glm4_moe_lite")
    d = _record()["dims"]
    D, H = 64, 4
    attn = (D * 24 + 24 * H * 16 + D * 20 + 16 * H * 28 + H * 16 * D)
    dense = attn + 3 * D * 160
    # a token's two routed experts and the shared one, and the router
    expert = attn + D * 8 + 3 * D * 48 * (2 + 1)
    pair = 2 * H * (12 + 4 + 16)  # q.k over nope + rope, p.v over v
    want = (2 * (dense + 2 * expert) * 7 + pair * 3 * 50
            + 2 * D * 256 * 2)
    assert ref.forward_flops(d, 7, 50, 2) == want
    # a decode step: every expert stored, bfloat16, and 20 values a live
    # token a layer whatever the leaf pads them to
    stored = dense + 2 * (attn + D * 8 + 3 * D * 48 * (8 + 1))
    assert ref.decode_step_bytes(d, 30, 4) == 2.0 * (
        stored + D * 256 + 4 * D + 20 * 3 * 30
    )


def test_decode_step_hbm_roofline():
    run = _record()
    ref = common.load_by_path("references", "glm4_moe_lite")
    # the traced steps with lanes running hold 100 and 60 live tokens; the
    # decode program took 10 and 30 ms
    least = ref.decode_step_bytes(run["dims"], 80.0, 4) / (
        PEAKS[KIND]["hbm_bytes_per_s"]
    )
    assert reader("decode_step_hbm_roofline")(run) == pytest.approx(
        100.0 * least / 0.02
    )


@pytest.mark.parametrize("name", [
    "serve_model_mfu", "decode_step_hbm_roofline",
])
def test_the_readers_find_nothing_where_there_is_nothing(name):
    run = _record()
    assert reader(name)({}) is None
    # a configuration whose reference has no such count (GPT-2's)
    other = dict(run, cell=types.SimpleNamespace(
        config={"reference": "gpt2"}
    ))
    assert reader(name)(other) is None
    if name == "decode_step_hbm_roofline":
        assert reader(name)({**run, "trace": None}) is None  # untraced
        run["trace"]["trace"].devices[0].modules = []  # no decode program
        assert reader(name)(run) is None
    else:
        assert reader(name)({**run, "window_s": 0}) is None
