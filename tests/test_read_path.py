"""The decode read path is the engine's choice (``engine.read_path``): the
rule as one table, the config-time check and the engine agreeing on it,
the counters that report it, and a demanded in-place read (interpret
mode) serving a demanded gather's tokens."""

import dataclasses
import itertools

import numpy as np
import pytest

import jax

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import (
    Config,
    ModelConfig,
    ServingConfig,
)
from distributeddeeplearning_tpu.serving import (
    Request,
    ServingEngine,
    check_serving_composition,
)
from distributeddeeplearning_tpu.serving.engine import (
    ATTN_KERNELS, READ_PATHS, read_path,
)
from distributeddeeplearning_tpu.telemetry import Telemetry

_CFG = ServingConfig(
    slots=3, block_size=8, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16),
)
_FITS = dict(
    platform="tpu", latent=False, window=False, width=768, kv_quant="off",
    speculation="off", block_size=16,
)


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


# ---------------------------------------------------------------------------
# The rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "platform,latent,window,width,kv_quant,speculation,block_size",
    itertools.product(
        ("tpu", "cpu"), (False, True), (False, True), (768, 64),
        ("off", "int8"), ("off", "ngram:2"), (16, 4),
    ),
)
def test_rule_takes_the_in_place_read_only_where_every_fact_allows(
        platform, latent, window, width, kv_quant, speculation, block_size):
    facts = dict(
        platform=platform, latent=latent, window=window, width=width,
        kv_quant=kv_quant, speculation=speculation, block_size=block_size,
    )
    want = "in_place" if facts == _FITS else "gather"
    assert read_path("reference", **facts) == want
    # A demand is taken as asked, whatever the facts (the fences have
    # refused by name what is not built before the rule is asked).
    assert read_path("pallas", **facts) == "in_place"
    assert read_path("gather", **facts) == "gather"


@pytest.mark.parametrize("fact,value", [
    ("platform", "cpu"), ("platform", "gpu"), ("latent", True),
    ("window", True), ("width", 64), ("width", 192), ("kv_quant", "int8"),
    ("speculation", "ngram:1"), ("block_size", 12),
])
def test_each_fact_alone_sends_the_rule_to_the_gather(fact, value):
    assert read_path("reference", **_FITS) == "in_place"
    assert read_path("reference", **{**_FITS, fact: value}) == "gather"


def test_domains():
    assert ATTN_KERNELS == ("reference", "pallas", "gather")
    assert READ_PATHS == ("gather", "in_place")


# ---------------------------------------------------------------------------
# check_serving_composition and the engine share it
# ---------------------------------------------------------------------------

_TINY = dict(size="tiny", vocab_size=97, max_len=64)
# The tiny presets' K and V are under 128 wide (a padded pool, which the
# chip cannot copy pages out of): whole 128-lane rows for the cases that
# should read in place.
_WIDE = dict(_TINY, embed_dim=128, num_heads=2)
_WIDE_GQA = dict(_TINY, embed_dim=256, num_heads=4, num_kv_heads=2)
# model, its kwargs, serving overrides -> the path on a TPU
_CASES = {
    "gpt2": ("gpt2", _WIDE, {}, "in_place"),
    "llama_gqa": ("llama", _WIDE_GQA, {}, "in_place"),
    "gpt2_toy_width": ("gpt2", _TINY, {}, "gather"),
    "gpt2_block4": ("gpt2", _TINY, dict(block_size=4), "gather"),
    "gpt2_int8": ("gpt2", _TINY, dict(kv_quant="int8"), "gather"),
    "gpt2_speculating": ("gpt2", _TINY, dict(speculation="ngram:2"), "gather"),
    "gpt2_prefix_cache": ("gpt2", _WIDE, dict(prefix_cache=True), "in_place"),
    "gpt2_demands_gather": ("gpt2", _TINY, dict(attn_kernel="gather"),
                            "gather"),
    "glm_latent": ("glm4_moe_lite", _TINY, dict(hbm_budget_mb=1), "gather"),
    "cohere_window": (
        "cohere2_moe", dict(_TINY, held_experts=(0, 4)),
        dict(block_size=8, hbm_budget_mb=1), "gather",
    ),
}


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("case", list(_CASES))
def test_config_check_and_engine_agree(case, platform):
    name, kwargs, overrides, on_tpu = _CASES[case]
    serving = dataclasses.replace(_CFG, **overrides)
    cfg = Config(model=ModelConfig(name=name, kwargs=dict(kwargs)),
                 serving=serving)
    want = on_tpu if platform == "tpu" else "gather"
    assert check_serving_composition(cfg, platform=platform) == want
    # No platform asked about: nothing answered (a fleet's parent calls
    # the check and must not touch the backend).
    assert check_serving_composition(cfg) is None
    model = models.get_model(name, **kwargs)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(model, params, serving, platform=platform)
    assert eng.read_path == want
    st = eng.stats()
    assert st["read_path"] == want
    assert st["attn_kernel"] == serving.attn_kernel  # what was asked
    assert eng.scheduler.gauges()["read_path"] == want
    # What the model is handed is the path, not the demand.
    paged = getattr(eng.model, "paged_kernel", "reference")
    assert paged == ("pallas" if want == "in_place" else "reference")


def test_cpu_engine_that_demands_nothing_gathers():
    model = models.get_model("gpt2", **_TINY)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(model, params, _CFG)  # platform: this process's
    assert jax.default_backend() == "cpu"
    assert eng.stats()["read_path"] == "gather"
    assert eng.model.paged_kernel == "reference"


@pytest.mark.parametrize("kernel", ["cuda", "in_place", ""])
def test_unknown_demand_is_refused_by_both(kernel):
    serving = dataclasses.replace(_CFG, attn_kernel=kernel)
    with pytest.raises(ValueError, match="attn_kernel must be one of"):
        check_serving_composition(Config(
            model=ModelConfig(name="gpt2", kwargs=dict(_TINY)),
            serving=serving,
        ))
    model = models.get_model("gpt2", **_TINY)
    with pytest.raises(ValueError, match="attn_kernel must be one of"):
        ServingEngine(model, {}, serving)


@pytest.mark.parametrize("name,kwargs,overrides,text", [
    ("glm4_moe_lite", _TINY, dict(hbm_budget_mb=1),
     r"attn_kernel='pallas' x latent paged cache"),
    ("cohere2_moe", dict(_TINY, held_experts=(0, 4)), dict(hbm_budget_mb=1),
     r"attn_kernel='pallas' x window layers"),
    ("gpt2", _TINY, dict(speculation="ngram:2"),
     r"speculation='ngram:2' x attn_kernel='pallas'"),
    ("gpt2", _TINY, dict(block_size=4),
     r"attn_kernel='pallas' x block_size=4"),
], ids=["latent", "window", "speculation", "block_size"])
def test_demanded_kernel_keeps_its_fences_and_a_demanded_gather_passes(
        name, kwargs, overrides, text):
    def cfg(kernel):
        return Config(
            model=ModelConfig(name=name, kwargs=dict(kwargs)),
            serving=dataclasses.replace(
                _CFG, attn_kernel=kernel, **overrides),
        )

    with pytest.raises(NotImplementedError, match=text):
        check_serving_composition(cfg("pallas"), platform="tpu")
    for kernel in ("gather", "reference"):
        assert check_serving_composition(
            cfg(kernel), platform="tpu") == "gather"


# ---------------------------------------------------------------------------
# The two paths serve the same tokens
# ---------------------------------------------------------------------------


def _serve(name, kernel, *, tel=None):
    """Five requests over three lanes: two join mid-flight as lanes
    retire, and at the end one lane decodes alone beside two idle ones
    (cursor 0, table on the null block)."""
    model = models.get_model(name, **_TINY)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(
        model, params, dataclasses.replace(_CFG, attn_kernel=kernel),
        clock=_fake_clock(), telemetry=tel,
    )
    rng = np.random.default_rng(42)
    for n, new in ((5, 4), (9, 7), (3, 3), (12, 6), (7, 19)):
        eng.submit(Request(
            prompt=list(map(int, rng.integers(1, 97, n))),
            max_new_tokens=new,
        ))
    idle_seen = False
    while eng.step():
        idle_seen |= len(eng.scheduler.active) == 1
    assert idle_seen
    done = sorted(eng.scheduler.finished, key=lambda s: s.request.request_id)
    return eng, [st.generated for st in done]


@pytest.mark.interpret
@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_demanded_in_place_serves_the_demanded_gathers_tokens(name, tmp_path):
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    gather, want = _serve(name, "gather")
    in_place, got = _serve(name, "pallas", tel=tel)
    assert gather.stats()["read_path"] == "gather"
    assert in_place.stats()["read_path"] == "in_place"
    assert [len(g) for g in got] == [4, 7, 3, 6, 19]
    assert got == want
    # The decode span says which path its program read by.
    spans = [s for s in tel.tracer.spans if s.name == "decode"]
    assert spans and {s.args["read_path"] for s in spans} == {"in_place"}
    assert {s.args["sampler"] for s in spans} == {"greedy"}


@pytest.mark.interpret
@pytest.mark.parametrize("kernel", ["pallas", "gather", "reference"])
def test_compile_count_pinned_whatever_is_demanded(kernel):
    # One executable a bucket and one decode; traffic never recompiles.
    model = models.get_model("gpt2", **_TINY)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    cfg = dataclasses.replace(_CFG, attn_kernel=kernel)
    eng = ServingEngine(model, params, cfg, clock=_fake_clock())
    eng.warmup()
    expected = len(cfg.prompt_buckets) + 1
    assert eng.num_compiles == expected
    rng = np.random.default_rng(0)
    for plen, new in [(3, 2), (9, 4), (16, 1)]:
        eng.submit(Request(
            prompt=list(map(int, rng.integers(1, 97, plen))),
            max_new_tokens=new,
        ))
    eng.run()
    assert eng.num_compiles == expected
