"""``correct`` is a comparison that has been shown to fail: at a size a test
run can hold (``data/gpt2_tiny.json``, CPU), the control comes out not
correct, and so does a run whose timed path is broken underneath.

Each fault test skips the harness's look for a chip and drives the rest of a
run (``run.run_cell``), with the fault planted in the program.
"""

import io
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import common, correct, faults

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "gpt2_tiny.json")
SEED = 2**31 + 11


def _cell(traffic):
    cell = common.make_cell(
        "tiny", 1, TINY, os.path.join(HERE, "data", traffic + ".json"),
        end_to_end=[], per_layer=[],
    )
    if cell.traffic["driver"] == "serve_loop":
        # the fixture's overrides are a training configuration's: serve it
        # as configs/gpt2_small.json is served
        prog = cell.config["program"]
        prog["overrides"] = [
            o for o in prog["overrides"] if not o.startswith("data.seq_len")
        ] + ["data.seq_len={n_positions}", "data.batch_size=1",
             "model.kwargs.attn_impl=xla"]
    return cell


def _drive(traffic, hooks, monkeypatch, seconds=1.0):
    monkeypatch.setattr(
        common, "units_of", lambda: {
            k: "x" for k in ("setup_s", "train_samples_per_s",
                             "serve_tokens_per_s")
        },
    )
    buf = io.StringIO()
    ok = bench_run.run_cell(_cell(traffic), SEED, seconds, False,
                            hooks=hooks, out=buf)
    line = json.loads(buf.getvalue().splitlines()[-1])
    assert line["correct"] == ok
    assert list(line)[-1] == "checked"  # the comparisons come last
    return line


# -- the sound program, then everything that has to fail -------------------


@pytest.mark.parametrize("traffic,seconds", [("fit_tiny", 1.0), ("closed_tiny", 2.0)])
def test_a_sound_run_is_correct(traffic, seconds, monkeypatch):
    line = _drive(traffic, None, monkeypatch, seconds)
    assert line["correct"], line["checked"]


@pytest.mark.parametrize("traffic,plant,seconds", [
    ("fit_tiny", "control", 1.0),
    ("fit_tiny", "half_batch_reference", 1.0),
    ("fit_tiny", "unchanged_state", 1.0),
    ("fit_tiny", "half_batch", 1.0),
    ("closed_tiny", "control", 2.0),
    ("closed_tiny", "altered_token", 2.0),
])
def test_the_control_and_each_fault_come_out_not_correct(
        traffic, plant, seconds, monkeypatch):
    """Through the harness's own comparison and result line."""
    line = _drive(traffic, faults.hooks(plant), monkeypatch, seconds)
    assert not line["correct"], line["checked"]
    assert not all(
        c["value"] <= c["limit"] for c in line["checked"].values()
    )


def test_norm_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 1.0, "c": 2e-9}
    gap, at = correct.norm_gap(prog, ref)
    assert at == "a" and gap == pytest.approx(0.1)
    # a leaf that has not moved reads 1
    gap, at = correct.norm_gap({"a": 0.0, "b": 1.0, "c": 1e-9}, ref)
    assert at == "a" and gap == pytest.approx(1.0)
    assert correct.live_leaves({"a": 1.0, "b": 1.0, "c": 1e-9}) == ["a", "b"]
