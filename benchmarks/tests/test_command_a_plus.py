"""The Command A+ family through the harness, at a test's size on the CPU
(``data/command_a_plus_tiny.json`` over ``data/closed_tiny.json``: a chip
that holds experts 4-7 of 16, window 8, contexts up to 54): the
configuration loads and its overrides fill, the reference's weights rename
into the program's tree, a sound run is correct and the control and the
planted fault are not, through the harness's own comparison and result
line; the reference's counts by hand; and the reader this configuration
brings, on a recorded span list and on none.

The fixture's limit, from readings on this size (3 seeds, CPU), with the
reference's two constants set to the fixture's scale (scores of a 32-wide
router lie 0.005 apart, not 0.009 as at 4096 wide: margin 0.001; logits are
of size 0.7, not 4: allowance 0.3): sound runs 0.0003-0.0012, the fp8
control 0.0117-0.0230, an altered token 0.82-1.02; 0.005 is four times the
first and under half of the second.
"""

import io
import json
import os

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import serve_loop
from benchmarks.harness import common, faults, flops

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 11


def _cell():
    return common.make_cell(
        "command_a_plus_tiny", 1,
        os.path.join(HERE, "data", "command_a_plus_tiny.json"),
        os.path.join(HERE, "data", "closed_tiny.json"),
        end_to_end=[], per_layer=[],
    )


@pytest.fixture
def ref(monkeypatch):
    mod = common.load_by_path("references", "cohere2_moe")
    monkeypatch.setattr(mod, "ROUTING_MARGIN", 0.001)  # module docstring
    monkeypatch.setattr(mod, "OPEN_ALLOWANCE", 0.3)
    return mod


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


def test_the_configuration_loads_and_its_overrides_fill(ref):
    cell = _cell()
    d = ref.dims(cell.config)
    assert (d["num_experts"], d["published_experts"],
            d["held_first_expert"]) == (4, 16, 4)
    assert d["vocab_size"] == 256 and d["n_positions"] == 64
    cfg = serve_loop.program_config(cell, SEED)
    kw = cfg.model.kwargs
    assert kw["held_experts"] == (4, 4) and kw["num_routed_experts"] == 16
    assert kw["layer_types"] == tuple(cell.config["layer_types"])
    assert kw["window"] == 8 and kw["shared_combine"] == "average"
    assert cfg.serving.prompt_buckets == (8, 16, 32)


def test_the_cells_own_file_keeps_every_published_width():
    cell = common.load_cell("serve_command_a_plus_closed16")
    c = cell.config
    assert [c[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "num_experts_per_tok",
        "num_shared_experts", "sliding_window",
    )] == [4096, 128, 8, 128, 4096, 8, 4, 4096]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
    }
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 32768)
    assert set(c["reduced_why"]) == set(c["reduced"])
    t = cell.traffic
    assert t["arrivals"] == {"kind": "closed", "clients": 16}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.8, "min": 512, "max": 12288}
    assert t["output_len"] == {"dist": "exponential", "mean": 400,
                               "min": 32, "max": 1024}
    assert (t["ramp_seconds"], t["check_requests"]) == (10, 4)
    names = {m["name"] for m in cell.per_layer}
    assert {"window_pages_ms_per_step", "decode_step_hbm_roofline",
            "serve_model_mfu", "kv_blocks_in_use_share"} <= names
    assert not {"serve_mfu", "decode_hbm_roofline"} & names  # GPT-2's counts


def test_the_references_weights_rename_into_the_programs_tree(ref):
    import flax

    from distributeddeeplearning_tpu import models

    cell = _cell()
    d = ref.dims(cell.config)
    cfg = serve_loop.program_config(cell, SEED)
    model = models.get_model(cfg.model.name, **cfg.model.kwargs)
    want = flax.core.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")
    )["params"])
    got = jax.eval_shape(
        lambda k: ref.program_tree(ref.weights_from_key(k, d), d),
        ref.seed_key(SEED),
    )
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(got) == shapes(want)
    names = ref.leaf_names_of_program_tree(got)
    assert names["block_3.moe.experts_gate"].shape == (4, 32, 24)
    assert names["block_0.moe.router"].shape == (32, 16)
    assert "lm_head" not in names  # the embedding is the head


def test_a_sound_run_is_correct(ref, monkeypatch):
    line = _drive(None, monkeypatch)
    assert line["correct"], line["checked"]
    assert line["checked_tokens"] > 100


@pytest.mark.parametrize("plant", ["control", "altered_token"])
def test_the_control_and_the_fault_come_out_not_correct(
        ref, plant, monkeypatch):
    line = _drive(faults.hooks(plant), monkeypatch)
    assert not line["correct"], line["checked"]
    gap = line["checked"]["logit_gap"]
    assert gap["value"] > gap["limit"]


# -- the counts, by hand ---------------------------------------------------


def test_forward_flops_and_step_bytes_by_hand(ref):
    d = ref.dims(_cell().config)
    D, F = 32, 24
    attn = 2 * D * 8 * (8 + 2)  # q and o on 8 heads, k and v on 2
    # a token multiplies attention, the 16-wide router, two shared experts
    # and, where routing is even, 4 x 4 / 16 = 1 routed expert
    outside = attn + D * 16 + 2 * 3 * D * F
    per_token = 2 * 4 * (outside + 1.0 * 3 * D * F)
    pair = 4 * 8 * 8  # q.k and p.v over 8 dims on 8 query heads
    head = 2 * D * 256
    # a prompt of 20 from position 0: the global layer sees the ramp, each
    # of the three window layers 8 keys a query past the eighth
    ramp = flops.causal_ctx_sum(20)
    assert ramp == 210
    want = per_token * 20 + pair * (210 + 3 * (36 + 12 * 8)) + head * 1
    assert ref.forward_flops(d, 20, ramp, 1) == want
    # 10 decode tokens over 300 keys in all (30 a token): window layers 8
    want = per_token * 10 + pair * (300 + 3 * 10 * 8) + head * 10
    assert ref.forward_flops(d, 10, 300, 10) == want
    # a decode step of 4 lanes holding 50 tokens in all: of the 4 held
    # experts a layer 4 x (1 - (3/4)^4) are touched; K and V are 2 x 2 x 8
    # values a token a layer, all 50 in the global layer and min(50, 4 x 8)
    # in each window layer
    touched = 4 * (1 - 0.75 ** 4)
    weights = 4 * (outside + touched * 3 * D * F) + D * 256
    assert ref.decode_step_bytes(d, 50, 4) == pytest.approx(
        2.0 * (weights + 32 * (50 + 3 * 32))
    )


def test_the_cells_step_reads_what_the_issue_reckoned():
    ref = common.load_by_path("references", "cohere2_moe")
    cell = common.load_cell("serve_command_a_plus_closed16")
    d = ref.dims(cell.config)
    # 16 lanes at the traffic's mean of 5.3k tokens: about 8.2 GB a step
    assert ref.decode_step_bytes(d, 16 * 5271, 16) == pytest.approx(
        8.3e9, rel=0.02
    )
    # a mean prefill (5,086 tokens) is about 19 TFLOP
    assert ref.forward_flops(
        d, 5086, flops.causal_ctx_sum(5086), 1
    ) == pytest.approx(19e12, rel=0.1)


# -- the reader ------------------------------------------------------------


def test_window_pages_ms_per_step_reads_its_spans():
    read = common.load_by_path(
        "layer_metrics", "window_pages_ms_per_step"
    ).read
    spans = [
        # (name, start, end, depth): two steps in the window, each with a
        # window_pages span inside decode_prepare; one admission's; and a
        # step before the window, which does not count
        ("decode_prepare", 0.5, 0.6, 1), ("window_pages", 0.5, 0.55, 2),
        ("decode_prepare", 1.0, 1.010, 1), ("window_pages", 1.0, 1.002, 2),
        ("window_pages", 1.5, 1.501, 2),
        ("decode_prepare", 2.0, 2.010, 1), ("window_pages", 2.0, 2.003, 2),
    ]
    run = {"window": (0.9, 3.0), "spans": spans}
    assert read(run) == pytest.approx(1e3 * (0.002 + 0.001 + 0.003) / 2)
    # a program that records no such span (a model without window layers,
    # the parent commit), an untraced run, an empty record: nothing to read
    no_pages = [s for s in spans if s[0] != "window_pages"]
    assert read({"window": (0.9, 3.0), "spans": no_pages}) is None
    assert read({"window": (0.9, 3.0), "spans": []}) is None
    assert read({}) is None
