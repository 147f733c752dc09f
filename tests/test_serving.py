"""Serving engine (serving/engine.py): paged-KV correctness against
generate(), zero-recompile steady state, per-request isolation, int8
weight quantization, lifecycle events, config wiring, and the
composition fences."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import (
    Config,
    ModelConfig,
    ServingConfig,
    apply_overrides,
)
from distributeddeeplearning_tpu.generate import (
    _filter_logits, generate, pad_prompts,
)
from distributeddeeplearning_tpu.serving import (
    Request,
    ServingEngine,
    check_serving_composition,
)
from distributeddeeplearning_tpu.serving.engine import (
    SAMPLER_ARMS, sampler_arm,
)

_CFG = ServingConfig(
    slots=3, block_size=4, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16),
)


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


def _model_and_params(name, seed=7):
    model = models.get_model(name, size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(seed), np.zeros((1, 8), np.int32)
    )["params"]
    return model, params


def _prompts(lens, seed=42):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 97, n))) for n in lens]


def _engine(model, params, cfg=_CFG, **kw):
    return ServingEngine(model, params, cfg, clock=_fake_clock(), **kw)


# ---------------------------------------------------------------------------
# Correctness: continuous batching == generate(), token for token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_engine_greedy_matches_generate(name):
    # 5 requests over 3 lanes: lanes retire and refill mid-flight, prompts
    # span both buckets — and every request's greedy tokens must equal a
    # plain generate() of that prompt (paged cache + continuous batching
    # change the SCHEDULE, never the numbers). Llama covers the GQA path.
    model, params = _model_and_params(name)
    prompts = _prompts((5, 9, 3, 12, 7))
    padded, lens = pad_prompts(prompts, pad_id=0)
    ref = np.asarray(generate(
        model, params, padded, max_new_tokens=11, prompt_lens=lens
    ))[:, -11:]
    eng = _engine(model, params)
    for p in prompts:
        eng.submit(Request(prompt=p, max_new_tokens=11))
    done = eng.run()
    assert len(done) == len(prompts)
    assert eng.scheduler.stats()["used_blocks"] == 0  # all pages released
    for i, st in enumerate(done):
        assert st.generated == list(ref[i]), f"request {i}"


def _leaves_named(cache, names):
    return [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if getattr(path[-1], "key", None) in names
    ]


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_pool_block_bytes_are_the_kv_written_for_it(name):
    # A pool leaf is [num_blocks, block_size, kv_heads*head_dim] (heads
    # folded into the minor dimension: transformer.paged_decode_attention
    # says why), and a block's bytes in C order are the K/V of its
    # block_size positions, head-major within a position. Pin both against
    # the OTHER cache: what generate()'s contiguous [B, T, kv_heads, D]
    # cache holds for the same prompt, read from the pool the way spill
    # and handoff read it. Llama folds 4 query heads onto 2 kv heads: the
    # pool is stored pre-repeat.
    from distributeddeeplearning_tpu.generate import prefill

    model, params = _model_and_params(name)
    bs = _CFG.block_size
    prompt = _prompts((11,))[0]
    dec = model.clone(decode=True)
    tokens = np.asarray([prompt], np.int32)
    cache = dec.init(jax.random.PRNGKey(0), tokens)["cache"]
    _, cache = prefill(dec, params, cache, tokens)
    want = _leaves_named(cache, ("cached_key", "cached_value"))

    eng = _engine(model, params)
    st = eng.submit(Request(prompt=prompt, max_new_tokens=4))
    eng.step()  # admit, prefill, one decode: the lane is still live
    assert not st.done
    leaves = eng._pool_leaves()
    assert len(leaves) == len(want) > 0
    _, _, kv_heads, head_dim = want[0].shape
    for leaf in leaves:
        assert leaf.shape == (eng.num_blocks, bs, kv_heads * head_dim)
    blocks = [int(b) for b in eng._table[st.slot, :len(prompt) // bs]]
    assert 0 not in blocks  # the null block holds no request's KV
    eng._spill_out([(b, bytes([j])) for j, b in enumerate(blocks)])
    for j in range(len(blocks)):
        codec, rows = eng._spill_store[bytes([j])]
        assert codec == "fp"
        for row, ref in zip(rows, want):
            assert row.shape == (bs, kv_heads * head_dim)
            np.testing.assert_allclose(
                row,
                np.asarray(ref[0, j * bs:(j + 1) * bs]).reshape(bs, -1),
                rtol=1e-5, atol=1e-6,
            )


def test_mid_flight_join_uses_freed_slot_and_blocks():
    model, params = _model_and_params("gpt2")
    cfg = dataclasses.replace(_CFG, slots=2)
    eng = _engine(model, params, cfg)
    short = eng.submit(Request(prompt=_prompts((4,))[0], max_new_tokens=2))
    long = eng.submit(Request(prompt=_prompts((5,))[0], max_new_tokens=12))
    late = eng.submit(Request(prompt=_prompts((6,))[0], max_new_tokens=3))
    eng.run()
    # late could only run after short left; long never left its lane
    assert short.slot == late.slot
    assert long.finish_s > short.finish_s
    assert late.admit_s > short.finish_s - 1  # joined while long in flight
    assert late.admit_s < long.finish_s


# ---------------------------------------------------------------------------
# Zero recompiles in steady state (AOT executables, pinned counts)
# ---------------------------------------------------------------------------


def test_compile_count_is_pinned_across_traffic():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    eng.warmup()
    expected = len(_CFG.prompt_buckets) + 1  # per-bucket prefill + decode
    assert eng.num_compiles == expected
    # Traffic of every shape the engine admits: all buckets, varied
    # max_new, join/leave churn — compile count must not move.
    for plen, new in [(3, 2), (8, 5), (9, 7), (16, 1), (1, 9), (12, 4)]:
        eng.submit(Request(prompt=_prompts((plen,))[0], max_new_tokens=new))
    eng.run()
    assert eng.num_compiles == expected
    assert eng.calls["prefill"] == 6
    assert eng.calls["decode"] > 0


def test_lazy_compile_only_touched_buckets():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    eng.submit(Request(prompt=_prompts((4,))[0], max_new_tokens=2))
    eng.run()
    # bucket 8 + decode; bucket 16 never compiled
    assert eng.num_compiles == 2
    assert list(eng._prefill_exe) == [8]


# ---------------------------------------------------------------------------
# Per-request sampling isolation
# ---------------------------------------------------------------------------


def test_sampled_request_is_independent_of_batchmates():
    # A request's rng chain is fold_in(seed, request_id) and its logits see
    # only its own pages — so request 0's tokens must be identical no
    # matter what shares the batch with it.
    model, params = _model_and_params("gpt2")
    a = _prompts((6,))[0]
    outs = []
    for other_lens in ((3, 9), (11, 2)):
        eng = _engine(model, params, seed=5)
        first = eng.submit(Request(
            prompt=a, max_new_tokens=8, temperature=0.9, top_k=11,
        ))
        for p in _prompts(other_lens, seed=hash(other_lens) % 1000):
            eng.submit(Request(
                prompt=p, max_new_tokens=6, temperature=0.7, top_p=0.8,
            ))
        eng.run()
        outs.append(list(first.generated))
        assert all(0 <= t < 97 for t in first.generated)
    assert outs[0] == outs[1]


def test_greedy_and_sampled_mix_in_one_batch():
    model, params = _model_and_params("gpt2")
    prompts = _prompts((5, 5, 5))
    ref = np.asarray(generate(
        model, params, np.asarray([prompts[0]], np.int32), max_new_tokens=6
    ))[0, -6:]
    eng = _engine(model, params, seed=1)
    greedy = eng.submit(Request(prompt=prompts[0], max_new_tokens=6))
    eng.submit(Request(prompt=prompts[1], max_new_tokens=6,
                       temperature=1.2, top_k=13))
    eng.submit(Request(prompt=prompts[2], max_new_tokens=6,
                       temperature=0.6, top_p=0.7))
    eng.run()
    # the greedy lane is untouched by its sampled batchmates
    assert greedy.generated == list(ref)


# ---------------------------------------------------------------------------
# One conditional, three samplers (PR 29): the step picks the cheapest arm
# its lanes allow, and every arm gives the straight-line body's tokens
# ---------------------------------------------------------------------------


def _straight_line_sample_body(self, logits, rng, temp, top_k, top_p):
    """The whole of ``ServingEngine._sample_body`` before PR 29, kept as
    the oracle: sort, filter and draw for every lane of every call, then
    keep the argmax for the greedy ones."""
    greedy = jnp.argmax(logits, axis=-1)
    tempered = logits / jnp.where(temp > 0, temp, 1.0)[:, None]
    filtered = _filter_logits(tempered, top_k, top_p)
    split = jax.vmap(jax.random.split)(rng)  # [B, 2, 2]
    sampled = jax.vmap(jax.random.categorical)(split[:, 0], filtered)
    tok = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
    return tok, split[:, 1]


def _arm_by_hand(temp, top_k, top_p):
    """The rule of ``engine.sampler_arm`` lane by lane, in plain Python."""
    lanes = [(k, p) for t, k, p in zip(temp, top_k, top_p) if t > 0]
    if not lanes:
        return "greedy"
    return "filtered" if any(k > 0 or p > 0 for k, p in lanes) else "plain"


def _spy_on_programs(eng):
    """Compile every program and wrap each, so that the test sees the
    (temp, top_k, top_p) operands of every call the engine makes: the
    sampling operands are the last three of both signatures."""
    seen = []

    def spy(exe):
        def call(*args):
            seen.append(tuple(np.array(a) for a in args[-3:]))
            return exe(*args)
        return call

    eng.warmup()
    eng._decode_exe = spy(eng._decode_exe)
    eng._prefill_exe = {b: spy(e) for b, e in eng._prefill_exe.items()}
    return seen


_GREEDY, _PLAIN = {}, {"temperature": 0.9}
_TOP_K = {"temperature": 1.1, "top_k": 7}
_TOP_P = {"temperature": 0.7, "top_p": 0.8}
# Five requests over three lanes each: lanes retire and refill, so a mix
# passes through several arms and a lane changes its kind of request.
_SAMPLER_MIXES = {
    "all_greedy": [_GREEDY] * 5,
    "all_plain": [_PLAIN, {"temperature": 0.5}, _PLAIN, _PLAIN, _PLAIN],
    "all_filtered": [_TOP_K, _TOP_P, {**_TOP_K, "top_p": 0.9}, _TOP_P,
                     _TOP_K],
    "mixed": [_GREEDY, _PLAIN, _TOP_K, _GREEDY, _TOP_P],
    # a greedy lane's filters are never applied, so they choose no arm
    "greedy_with_filters": [{"top_k": 5}, {"top_p": 0.5}, _PLAIN,
                            {"top_k": 3, "top_p": 0.9}, _GREEDY],
}


def _serve_mix(model, params, mix, vocab, cfg=_CFG):
    eng = _engine(model, params, cfg, seed=3)
    seen = _spy_on_programs(eng)
    rng = np.random.default_rng(11)
    states = [
        eng.submit(Request(
            prompt=list(map(int, rng.integers(1, vocab, 4 + 3 * i))),
            max_new_tokens=4 + 2 * (i % 3), **kw,
        ))
        for i, kw in enumerate(mix)
    ]
    eng.run()
    return eng, seen, [list(st.generated) for st in states]


@pytest.mark.parametrize("mix", list(_SAMPLER_MIXES))
@pytest.mark.parametrize("name", ["gpt2", "glm4_moe_lite"])
def test_sampler_arms_give_the_straight_line_bodys_tokens(
        name, mix, monkeypatch):
    if name == "gpt2":
        (model, params), vocab = _model_and_params(name), 97
    else:
        model = models.get_model(name, size="tiny")
        params = model.init(
            jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
        )["params"]
        vocab = 256
    eng, seen, got = _serve_mix(model, params, _SAMPLER_MIXES[mix], vocab)
    # The counters say which arm each call's OPERANDS select, call by call.
    want = dict.fromkeys(SAMPLER_ARMS, 0)
    for operands in seen:
        want[_arm_by_hand(*operands)] += 1
    st = eng.stats()
    assert st["sampler"] == want
    assert sum(want.values()) == (
        st["calls"]["prefill"] + st["calls"]["decode"]
    ) == len(seen)
    if mix == "all_greedy":
        assert want["plain"] == want["filtered"] == 0
    elif mix in ("all_plain", "greedy_with_filters"):
        assert want["plain"] > 0 and want["filtered"] == 0
    elif mix == "all_filtered":
        assert want["greedy"] == want["plain"] == 0
    else:
        assert all(want.values()), want
    # Token for token what the straight-line body gives, sampled or greedy.
    monkeypatch.setattr(
        ServingEngine, "_sample_body", _straight_line_sample_body
    )
    _, _, oracle = _serve_mix(model, params, _SAMPLER_MIXES[mix], vocab)
    assert got == oracle


def test_sampler_arm_is_one_rule_for_host_and_program():
    cases = [
        ([0, 0, 0], [0, 0, 0], [0, 0, 0]),
        ([0, 0, 0], [4, 0, 0], [0, .5, 0]),
        ([0, .7, 0], [4, 0, 0], [0, 0, .5]),
        ([0, .7, 0], [0, 3, 0], [0, 0, 0]),
        ([.2, .7, 1.], [0, 0, 0], [0, .9, 0]),
    ]
    for temp, top_k, top_p in cases:
        t, k, p = np.float32(temp), np.int32(top_k), np.float32(top_p)
        arm = int(sampler_arm(t, k, p))
        assert SAMPLER_ARMS[arm] == _arm_by_hand(t, k, p)
        assert int(jax.jit(sampler_arm)(t, k, p)) == arm


def test_sampled_request_after_greedy_steps_draws_as_on_a_fresh_engine():
    # The greedy arm leaves the rng rows unsplit, where the straight-line
    # body split every row every call: a lane's chain must not depend on
    # how many such calls went before, because admission re-seeds it.
    model, params = _model_and_params("gpt2")
    cfg = dataclasses.replace(_CFG, slots=1)
    sampled = dict(prompt=_prompts((6,))[0], max_new_tokens=8,
                   temperature=0.9, top_k=11, request_id=9)
    fresh = _engine(model, params, cfg, seed=5)
    want = fresh.submit(Request(**sampled))
    fresh.run()

    eng = _engine(model, params, cfg, seed=5)
    eng.submit(Request(prompt=_prompts((5,))[0], max_new_tokens=7))
    eng.run()
    assert eng.stats()["sampler"] == {"greedy": 7, "plain": 0, "filtered": 0}
    got = eng.submit(Request(**sampled))  # the one lane again
    eng.run()
    assert got.slot == 0 and got.generated == want.generated
    assert eng.stats()["sampler"]["filtered"] == 8


@pytest.mark.parametrize("path", [
    "max_new_in_decode", "max_new_in_prefill", "eos", "handoff",
    "handoff_decode_route",
])
def test_freed_sampled_lane_returns_the_engine_to_the_greedy_arm(path):
    # Every way a lane is freed (scheduler.complete from a decode step or
    # from the prefill itself, on the token count or on EOS;
    # scheduler.complete_handoff after a prefill or on a full-prefix hit)
    # zeroes the lane's temperature, so a stale lane cannot hold the next
    # call on a sampling arm.
    model, params = _model_and_params("gpt2")
    cfg, kw = dataclasses.replace(_CFG, slots=2), {}
    sampled = dict(prompt=_prompts((6,))[0], max_new_tokens=4,
                   temperature=0.9, top_p=0.8, request_id=1)
    if path == "max_new_in_prefill":
        sampled["max_new_tokens"] = 1
    elif path == "eos":
        probe = _engine(model, params, cfg)
        toks = probe.submit(Request(**{**sampled, "max_new_tokens": 12}))
        probe.run()
        sampled["max_new_tokens"] = 12
        kw["eos_id"] = toks.generated[2]
        assert kw["eos_id"] not in toks.generated[:2]
    elif path.startswith("handoff"):
        kw.update(role="prefill", prefix_cache=True)
    eng = _engine(model, params, dataclasses.replace(cfg, **kw))
    if path == "handoff_decode_route":
        # The same prompt first: the second admission is a full-prefix hit
        # and hands off without a forward pass.
        eng.submit(Request(**{**sampled, "request_id": 0}))
        eng.run()
    st = eng.submit(Request(**sampled))
    if not path.startswith("handoff"):
        eng.submit(Request(prompt=_prompts((7,))[0], max_new_tokens=16,
                           request_id=2))
    for _ in range(40):
        if st.done or eng._handoffs:
            break
        eng.step()
    assert st.done or eng._handoffs
    assert not eng._temp.any()
    if path.startswith("handoff"):
        assert len(eng.take_handoffs()) == 1
        return
    if path == "eos":
        assert st.generated[-1] == kw["eos_id"] and len(st.generated) == 3
    before = eng.stats()["sampler"]
    assert before["filtered"] > 0
    assert eng.step()  # the greedy batchmate decodes on
    after = eng.stats()["sampler"]
    assert after == {**before, "greedy": before["greedy"] + 1}


def test_sampler_arm_rides_the_decode_span_and_the_gauges(tmp_path):
    from distributeddeeplearning_tpu.telemetry import Telemetry

    model, params = _model_and_params("gpt2")
    tel = Telemetry(enabled=True, out_dir=str(tmp_path))
    eng = _engine(model, params, dataclasses.replace(_CFG, gauge_every=1),
                  telemetry=tel)
    eng.submit(Request(prompt=_prompts((5,))[0], max_new_tokens=6))
    eng.submit(Request(prompt=_prompts((4,))[0], max_new_tokens=3,
                       temperature=0.8))
    eng.run()
    arms = [sp.args["sampler"] for sp in tel.tracer.spans
            if sp.name == "decode"]
    assert arms == ["plain"] * 2 + ["greedy"] * 3
    counts = eng.stats()["sampler"]
    assert counts == {"greedy": 4, "plain": 3, "filtered": 0}
    # The gauge block runs before the step's decode call: one behind.
    last = tel.stats_dict()["gauges"]["last"]
    assert last["sampler_plain"] == 3 and last["sampler_filtered"] == 0
    assert last["sampler_greedy"] == 3


# ---------------------------------------------------------------------------
# int8 weight-quantized serving
# ---------------------------------------------------------------------------


def test_int8_quant_mode_serves_and_reports():
    model, params = _model_and_params("llama")
    cfg = dataclasses.replace(_CFG, quant="int8", quant_block=64)
    eng = _engine(model, params, cfg)
    rep = eng.quant_report
    assert rep["param_bytes_quant"] < 0.35 * rep["param_bytes_fp"]
    assert rep["max_rel_error"] < 0.05
    states = [
        eng.submit(Request(prompt=p, max_new_tokens=6))
        for p in _prompts((4, 7))
    ]
    eng.run()
    for st in states:
        assert len(st.generated) == 6
        assert all(0 <= t < 97 for t in st.generated)


def test_quantized_leaf_roundtrip_error_is_small():
    from distributeddeeplearning_tpu.serving.quant import (
        dequantize_params,
        quantize_params,
    )

    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(32, 48)).astype(np.float32),
              "b": rng.normal(size=(48,)).astype(np.float32)}
    tree, report = quantize_params(params, block_size=64)
    back = dequantize_params(tree)
    assert back["b"] is params["b"]  # 1-D leaves pass through untouched
    assert back["w"].shape == (32, 48)
    err = np.abs(np.asarray(back["w"]) - params["w"]).max()
    assert err < np.abs(params["w"]).max() / 100
    assert report["ratio"] < 0.5


# ---------------------------------------------------------------------------
# Lifecycle events (metrics.serving_event)
# ---------------------------------------------------------------------------


def test_event_stream_per_request_lifecycle():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    states = [
        eng.submit(Request(prompt=p, max_new_tokens=3))
        for p in _prompts((4, 6, 5, 3))
    ]
    eng.run()
    for st in states:
        rid = st.request.request_id
        mine = [e for e in eng.events if e["request_id"] == rid]
        names = [e["event"] for e in mine]
        assert names == ["request_admitted", "first_token",
                         "request_completed"]
        admitted, first, completed = mine
        assert admitted["bucket"] == st.bucket
        assert first["ttft_s"] >= 0
        assert completed["new_tokens"] == 3
    # events ride the engine step counter monotonically
    steps = [e["step"] for e in eng.events]
    assert steps == sorted(steps)


def test_serving_event_rejects_unknown_name():
    from distributeddeeplearning_tpu.metrics import serving_event

    with pytest.raises(ValueError, match="unknown serving event"):
        serving_event("request_vanished", 0, request_id=1)


# ---------------------------------------------------------------------------
# Config wiring + composition fences
# ---------------------------------------------------------------------------


def _cfg(name="gpt2", model_kwargs=None, serving=None):
    return Config(
        model=ModelConfig(name=name, kwargs=model_kwargs or {}),
        serving=serving or ServingConfig(),
    )


def test_serving_config_overrides_wire_through():
    cfg = apply_overrides(_cfg(), [
        "serving.slots=8", "serving.quant=int8",
        "serving.prompt_buckets=(16,64)",
    ])
    assert cfg.serving.slots == 8
    assert cfg.serving.quant == "int8"
    assert cfg.serving.prompt_buckets == (16, 64)


def test_serving_block_rejects_scalar_override():
    with pytest.raises(ValueError, match=r"serving is a config block"):
        apply_overrides(_cfg(), ["serving=fast"])


def test_fence_pipelined_model():
    with pytest.raises(NotImplementedError, match="pipelined"):
        check_serving_composition(_cfg(name="gpt2_pp"))


def test_fence_capacity_moe():
    with pytest.raises(NotImplementedError, match="capacity-MoE"):
        check_serving_composition(_cfg(name="llama_moe"))


def test_fence_non_decode_model():
    with pytest.raises(ValueError, match="decode-capable"):
        check_serving_composition(_cfg(name="resnet18"))


def test_fence_fused_attention():
    with pytest.raises(NotImplementedError, match="attn_impl='xla'"):
        check_serving_composition(
            _cfg(model_kwargs={"attn_impl": "ulysses_flash"})
        )


def test_fence_bad_quant_and_buckets():
    with pytest.raises(ValueError, match="serving.quant"):
        check_serving_composition(
            _cfg(serving=ServingConfig(quant="fp4"))
        )
    with pytest.raises(ValueError, match="prompt_buckets"):
        check_serving_composition(
            _cfg(serving=ServingConfig(prompt_buckets=(64, 32)))
        )


def test_fence_xla_attn_passes():
    check_serving_composition(_cfg(name="llama"))
    check_serving_composition(_cfg(model_kwargs={"attn_impl": "xla"}))


def test_engine_rejects_undersized_hbm_budget():
    model, params = _model_and_params("gpt2")
    cfg = dataclasses.replace(_CFG, hbm_budget_mb=0)
    with pytest.raises(ValueError, match="hbm_budget_mb"):
        ServingEngine(model, params, cfg)


def test_engine_rejects_prompt_beyond_largest_bucket():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="largest"):
        eng.submit(Request(prompt=list(range(1, 20)), max_new_tokens=2))


# ---------------------------------------------------------------------------
# Pallas paged-attention hot path (serving.attn_kernel='pallas')
# ---------------------------------------------------------------------------

# block_size must be a multiple of 8 for the pallas kernel (sublane tile);
# everything else matches _CFG so the two modes schedule identically.
_PALLAS_CFG = dataclasses.replace(_CFG, block_size=8, attn_kernel="pallas")


@pytest.mark.interpret
@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_pallas_engine_greedy_matches_generate(name):
    # The whole hot path under the kernel: bulk prefill (gather path,
    # L>1), then every decode step reads the pool through the Pallas
    # kernel (interpret mode on CPU) — tokens must equal generate()
    # exactly, across mid-flight joins. Llama covers GQA (num_rep>1).
    model, params = _model_and_params(name)
    prompts = _prompts((5, 9, 3, 12))
    padded, lens = pad_prompts(prompts, pad_id=0)
    ref = np.asarray(generate(
        model, params, padded, max_new_tokens=6, prompt_lens=lens
    ))[:, -6:]
    eng = _engine(model, params, _PALLAS_CFG)
    assert eng.stats()["attn_kernel"] == "pallas"
    for p in prompts:
        eng.submit(Request(prompt=p, max_new_tokens=6))
    done = eng.run()
    assert len(done) == len(prompts)
    for i, st in enumerate(done):
        assert st.generated == list(ref[i]), f"request {i}"


@pytest.mark.interpret
def test_pallas_compile_count_pinned():
    # Kernel selection must not disturb the AOT contract: one executable
    # per bucket + one decode, and traffic never recompiles.
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params, _PALLAS_CFG)
    eng.warmup()
    expected = len(_PALLAS_CFG.prompt_buckets) + 1
    assert eng.num_compiles == expected
    for plen, new in [(3, 2), (9, 4), (16, 1)]:
        eng.submit(Request(prompt=_prompts((plen,))[0], max_new_tokens=new))
    eng.run()
    assert eng.num_compiles == expected


# ---------------------------------------------------------------------------
# Pool buffer donation (decode executable aliases the cache in place)
# ---------------------------------------------------------------------------


def test_decode_donation_counter_in_registry(tmp_path):
    from distributeddeeplearning_tpu.telemetry import Telemetry

    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params, telemetry=tel)
    eng.warmup()
    # The decode cache argument is donated: every pool/page-table/cursor
    # leaf aliases input->output instead of double-buffering the KV pool.
    dec = tel.registry.get("serving_decode")
    assert dec is not None and dec["donated_args"] > 0
    assert dec["donated_args"] == len(
        jax.tree_util.tree_leaves(eng._cache)
    )
    # Prefill deliberately is NOT donated (XLA:CPU aliased its [1]-shaped
    # token output with the donated seq_lens leaf and returned stale
    # bytes) — the registry records that decision as data.
    for b in _CFG.prompt_buckets:
        pre = tel.registry.get(f"serving_prefill_{b}")
        assert pre is not None and pre["donated_args"] == 0
    # Donation must not break serving: run traffic through the engine.
    st = eng.submit(Request(prompt=_prompts((5,))[0], max_new_tokens=4))
    eng.run()
    assert len(st.generated) == 4
    assert dec["recompiles"] == 0


def test_cache_warm_engine_serves_the_reference_tokens():
    # Serving warm-starts from the persistent compilation cache, donated
    # decode included: an engine whose every executable is a cache HIT must
    # serve generate()'s tokens. (An older jax returned stale bytes from a
    # cache-hit donated executable and the engine bypassed the cache; on
    # jax 0.9.0 it does not, here or on the chip — chip_smoke.py makes the
    # same comparison there.)
    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    model, params = _model_and_params("gpt2")
    prompts = _prompts((5, 9, 3, 12))
    padded, lens = pad_prompts(prompts, pad_id=0)
    ref = np.asarray(generate(
        model, params, padded, max_new_tokens=11, prompt_lens=lens
    ))[:, -11:]

    def serve():
        eng = _engine(model, params)
        eng.warmup()
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=11))
        return [st.generated for st in eng.run()]

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.monitoring.register_event_listener(on_event)
    try:
        # No compile-time floor: these tiny executables compile in well
        # under the 1 s below which the suite's cache persists nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        serve()  # fills the cache (or finds it filled by an earlier run)
        hits.clear()
        warm = serve()
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    assert len(hits) >= len(_CFG.prompt_buckets) + 1  # prefills + decode
    assert warm == [list(r) for r in ref]


# ---------------------------------------------------------------------------
# Page-table range safety (XLA gather clamps OOB indices silently)
# ---------------------------------------------------------------------------


def test_oob_host_page_table_fails_loudly():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    bad = np.zeros((eng.slots_n, eng.pages), np.int32)
    bad[1, 2] = eng.num_blocks  # one past the pool end
    with pytest.raises(ValueError, match="out of range"):
        eng._inject(eng._cache, bad, np.zeros((eng.slots_n,), np.int32))
    bad[1, 2] = -1
    with pytest.raises(ValueError, match="out of range"):
        eng._inject(eng._cache, bad, np.zeros((eng.slots_n,), np.int32))


def test_debug_checks_poison_oob_rows_to_nan():
    # Device-built tables bypass the host check; under train.debug_checks
    # (jax_enable_checks) the traced guard in paged_decode_attention
    # NaN-poisons exactly the rows whose table has an OOB entry.
    from distributeddeeplearning_tpu.generate import decode_step

    model, params = _model_and_params("gpt2")
    kv_pages = (8, 4, 3)
    pm = model.clone(decode=True, kv_pages=kv_pages)
    tok = np.zeros((2, 1), np.int32)
    shapes = jax.eval_shape(pm.init, jax.random.PRNGKey(0), tok)
    import jax.numpy as jnp

    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"]
    )

    def poison_tables(path, leaf):
        if getattr(path[-1], "key", None) == "page_table":
            t = np.zeros(leaf.shape, np.int32)
            t[1, 0] = kv_pages[0] + 5  # row 1 corrupt, row 0 clean
            return jnp.asarray(t)
        return leaf

    cache = jax.tree_util.tree_map_with_path(poison_tables, cache)
    jax.config.update("jax_enable_checks", True)
    try:
        logits, _ = decode_step(pm, params, cache, tok)
    finally:
        jax.config.update("jax_enable_checks", False)
    logits = np.asarray(logits)
    assert np.isnan(logits[1]).all()  # poisoned, loudly
    assert np.isfinite(logits[0]).all()  # clean row untouched


# ---------------------------------------------------------------------------
# Prefill/decode priority (serving.max_prefills_per_step)
# ---------------------------------------------------------------------------


def test_max_prefills_per_step_caps_admissions():
    model, params = _model_and_params("gpt2")
    cfg = dataclasses.replace(_CFG, slots=4, max_prefills_per_step=1)
    eng = _engine(model, params, cfg)
    states = [
        eng.submit(Request(prompt=p, max_new_tokens=5))
        for p in _prompts((4, 6, 3, 5))
    ]
    eng.run()
    # every request still completes (no starvation under the cap) ...
    assert all(len(st.generated) == 5 for st in states)
    # ... but no engine step ever ran more than one prefill
    per_step = {}
    for e in eng.events:
        if e["event"] == "request_admitted":
            per_step[e["step"]] = per_step.get(e["step"], 0) + 1
    assert per_step and max(per_step.values()) == 1
    # the burst drained one admission per step, in order
    assert sorted(per_step) == list(range(1, 5))


def test_max_prefills_cap_does_not_change_tokens():
    # Priority scheduling changes WHEN a request starts, never its tokens.
    model, params = _model_and_params("gpt2")
    prompts = _prompts((5, 7, 4))
    outs = []
    for cap in (0, 1):
        cfg = dataclasses.replace(_CFG, max_prefills_per_step=cap)
        eng = _engine(model, params, cfg)
        states = [
            eng.submit(Request(prompt=p, max_new_tokens=6))
            for p in prompts
        ]
        eng.run()
        outs.append([st.generated for st in states])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# New fences: attn_kernel and max_prefills_per_step
# ---------------------------------------------------------------------------


def test_fence_unknown_attn_kernel():
    with pytest.raises(ValueError, match="attn_kernel"):
        check_serving_composition(
            _cfg(serving=ServingConfig(attn_kernel="cuda"))
        )


def test_fence_pallas_needs_sublane_aligned_blocks():
    with pytest.raises(NotImplementedError, match="multiple of 8"):
        check_serving_composition(_cfg(serving=ServingConfig(
            attn_kernel="pallas", block_size=4,
        )))
    # aligned block sizes pass
    check_serving_composition(_cfg(serving=ServingConfig(
        attn_kernel="pallas", block_size=16,
    )))


def test_fence_negative_max_prefills():
    with pytest.raises(ValueError, match="max_prefills_per_step"):
        check_serving_composition(_cfg(serving=ServingConfig(
            max_prefills_per_step=-1,
        )))


# ---------------------------------------------------------------------------
# Quantized device-resident pool (serving.kv_quant='int8')
# ---------------------------------------------------------------------------

_INT8_CFG = dataclasses.replace(_CFG, kv_quant="int8")


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_int8_pool_greedy_matches_fp_engine(name):
    # The whole point of per-vector absmax scales on a tiny model:
    # greedy argmax survives int8 KV rounding token-for-token on the
    # standard trace (the engine drift probe bounds the logit gap; this
    # pins the token-level consequence). Llama covers GQA + RoPE.
    model, params = _model_and_params(name)
    prompts = _prompts((5, 9, 3, 12, 7))

    def run(cfg):
        eng = _engine(model, params, cfg)
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=11))
        return [st.generated for st in eng.run()], eng

    fp, _ = run(_CFG)
    q8, eng = run(_INT8_CFG)
    assert q8 == fp
    assert eng.scheduler.stats()["used_blocks"] == 0


def test_int8_pool_mints_proportionally_more_blocks():
    # Same HBM budget, >= 2x the blocks (ISSUE acceptance; measured
    # ratio is ~3.2x: int8 values + f32 scale overhead of 4/D per byte).
    model, params = _model_and_params("gpt2")
    fp = _engine(model, params)
    q8 = _engine(model, params, _INT8_CFG)
    assert q8.num_blocks >= 2 * fp.num_blocks
    assert q8.block_bytes < fp.block_bytes
    # The sizing probe saw the scale pools: bytes per block = int8 pool
    # bytes + f32 scales, nothing hand-modeled.
    s = q8.stats()
    assert s["kv_quant"] == "int8"
    assert s["kv_bytes_per_token"] == q8.block_bytes // _CFG.block_size


def test_int8_pool_compile_pin_and_cache_dtype():
    # Quantization changes the pool LAYOUT, not the executable count:
    # per-bucket prefill + decode, zero steady-state recompiles. The
    # cache really is int8 + f32 scales (not fp silently).
    import jax.numpy as jnp

    model, params = _model_and_params("gpt2")
    eng = _engine(model, params, _INT8_CFG)
    eng.warmup()
    expected = len(_CFG.prompt_buckets) + 1
    assert eng.num_compiles == expected
    for plen, new in [(3, 2), (8, 5), (16, 1), (12, 4)]:
        eng.submit(Request(prompt=_prompts((plen,))[0], max_new_tokens=new))
    eng.run()
    assert eng.num_compiles == expected
    flat = jax.tree_util.tree_flatten_with_path(eng._cache)[0]
    leaves = {p[-1].key: l for p, l in flat}
    assert leaves["pool_key"].dtype == jnp.int8
    assert leaves["pool_value"].dtype == jnp.int8
    assert leaves["pool_key_scale"].dtype == jnp.float32
    assert leaves["pool_value_scale"].dtype == jnp.float32
    # int8 values lane-dense like the fp pool; one scale per (slot, head).
    nb, bs = eng.num_blocks, _CFG.block_size
    heads, head_dim = model.num_heads, model.embed_dim // model.num_heads
    for kv in ("pool_key", "pool_value"):
        assert leaves[kv].shape == (nb, bs, heads * head_dim)
        assert leaves[kv + "_scale"].shape == (nb, bs, heads)


def test_int8_pool_pallas_matches_reference_engine():
    # Both read paths over the SAME quantized pool: the fused in-kernel
    # dequant and the gather reference agree token-for-token.
    model, params = _model_and_params("gpt2")
    prompts = _prompts((5, 9, 12))
    cfg_ref = dataclasses.replace(_INT8_CFG, block_size=8)
    cfg_pal = dataclasses.replace(
        _INT8_CFG, block_size=8, attn_kernel="pallas"
    )

    def run(cfg):
        eng = _engine(model, params, cfg)
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=9))
        return [st.generated for st in eng.run()]

    assert run(cfg_pal) == run(cfg_ref)


def test_int8_pool_gauges_carry_capacity_labels():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params, _INT8_CFG)
    g = eng.scheduler.gauges()
    assert g["kv_quant"] == "int8"
    assert g["kv_bytes_per_token"] == eng.block_bytes // _CFG.block_size
    fp = _engine(model, params)
    assert fp.scheduler.gauges()["kv_quant"] == "off"
