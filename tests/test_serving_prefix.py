"""Shared-prefix KV reuse on the serving engine (serving.prefix_cache):
exact greedy token parity between warm (trie-hit) and cold admissions —
both held to the no-cache full-forward reference — the
len(prompt_buckets)+len(suffix_buckets)+1 compile pin with zero
steady-state recompiles under warm/cold/decode-route traffic mix, the
full-prefix decode route, composition with speculative decoding and with
sampled requests sharing a prefix, eviction-pressure parity on a
deliberately tiny pool, the replica-probe surface
(``prefix_match_len``), and the telemetry rows (cached_tokens on
admission events, cached_prefill_skip histogram, prefix_hit_rate gauge).
Host-side trie/admission units live in tests/test_serving_units.py;
config-time fences in tests/test_composition_fences.py.
"""

import dataclasses

import numpy as np
import pytest

import jax

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import ServingConfig
from distributeddeeplearning_tpu.generate import greedy_agreement
from distributeddeeplearning_tpu.serving import (
    KVBlockPool,
    Request,
    ServingEngine,
)

_CFG = ServingConfig(
    slots=3, block_size=4, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16), prefix_cache=True, suffix_buckets=(4,),
)
_CFG_OFF = dataclasses.replace(_CFG, prefix_cache=False, suffix_buckets=())


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


def _shared_prefix_prompts(n, seed=3):
    """n prompts sharing one 8-token system prefix, suffixes 2..6 long."""
    rng = np.random.default_rng(seed)
    prefix = list(map(int, rng.integers(1, 97, 8)))
    return [prefix + list(map(int, rng.integers(1, 97, 2 + i % 5)))
            for i in range(n)]


def _run_waves(eng, waves, max_new=9, temperature=0.0):
    """Submit + run each wave to completion before the next (so wave k+1
    can hit KV published by wave k); returns per-wave generated tokens."""
    out = []
    for wave in waves:
        for p in wave:
            eng.submit(Request(prompt=list(p), max_new_tokens=max_new,
                               temperature=temperature))
        out.append([s.generated for s in eng.run()])
    return out


# ---------------------------------------------------------------------------
# Greedy parity: warm == cold == cache-off, and both match the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_warm_admissions_match_cache_off_engine(name):
    # The same two waves of shared-prefix traffic through a cache-on and
    # a cache-off engine: wave 2 on the cache-on engine is served warm
    # (suffix-only prefill / decode route) and must emit the identical
    # token streams. Cached-KV aliasing or an off-by-one in the suffix
    # cursor shifts tokens immediately.
    model, params = _model_and_params(name)
    waves = [_shared_prefix_prompts(4), _shared_prefix_prompts(4)]
    on = _engine(model, params)
    off = _engine(model, params, _CFG_OFF)
    got_on = _run_waves(on, waves)
    got_off = _run_waves(off, waves)
    assert got_on == got_off
    pc = on.stats()["prefix_cache"]
    assert pc["hit_tokens"] > 0, "wave 2 never hit the trie"
    assert "prefix_cache" not in off.stats()


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_warm_greedy_matches_full_forward_reference(name):
    # The generate-parity recipe (tests/test_generate_golden.py seeds and
    # shapes, max_new=11) submitted TWICE: the first wave runs cold and
    # seeds the trie; the second wave re-runs the identical prompts warm —
    # the 9-token prompt becomes a full-prefix decode-route admission, the
    # 5-token one a suffix-only prefill. Both waves are held to the
    # no-cache full-forward reference computed here on the same params, not
    # to each other — a bug that shifted warm and cold in lockstep still
    # fails.
    model, params = _model_and_params(name)
    prompts = _prompts((5, 9, 3))
    eng = _engine(model, params)
    cold, warm = _run_waves(eng, [prompts, prompts], max_new=11)
    for wave, got in (("cold", cold), ("warm", warm)):
        rec = greedy_agreement(model, params, prompts, got)
        assert rec["tokens"] == 3 * 11, wave
        assert rec["worst_logit_gap"] <= 1e-4, (wave, rec)
    pc = eng.stats()["prefix_cache"]
    assert pc["hit_tokens"] > 0
    assert pc["decode_route_admits"] >= 1  # the repeated 9-token prompt


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_multiturn_continuation_after_block_aligned_finish(name):
    # The motivating multi-turn workload crossed with a block-aligned
    # finish: turn 1 ends with len(prompt) + len(generated) an exact
    # multiple of block_size, and turn 2 replays the whole turn-1
    # sequence plus a follow-up. The final turn-1 token's KV was never
    # written (sampled, never fed back through the model), so its block
    # must be withheld from the trie at completion — a warm engine that
    # matched it would attend to garbage KV and diverge from cold.
    model, params = _model_and_params(name)
    prompts = _prompts((6, 6), seed=11)
    turns = []
    for cfg in (_CFG, _CFG_OFF):
        eng = _engine(model, params, cfg)
        (w1,) = _run_waves(eng, [prompts], max_new=6)  # 6+6: aligned
        assert all(len(g) == 6 for g in w1)
        follow = [list(p) + list(g) + [7, 3] for p, g in zip(prompts, w1)]
        (w2,) = _run_waves(eng, [follow], max_new=6)
        turns.append((w1, w2))
        if cfg.prefix_cache:
            # Per follow-up: 2 of the 3 matchable blocks are served warm
            # (8 tokens); the block holding the unwritten final-token KV
            # must not count as a hit.
            assert eng.stats()["prefix_cache"]["hit_tokens"] == 16
    assert turns[0] == turns[1]


def test_decode_route_skips_prefill_entirely():
    # A prompt extending a fully cached chain by one token takes the
    # decode route: no prefill call, first token from the next batched
    # decode step, and the stream matches the cache-off engine.
    model, params = _model_and_params("gpt2")
    (base,) = _prompts((8,), seed=11)
    ext = base + [33]
    on = _engine(model, params)
    off = _engine(model, params, _CFG_OFF)
    got_on = _run_waves(on, [[base], [ext]], max_new=7)
    got_off = _run_waves(off, [[base], [ext]], max_new=7)
    assert got_on == got_off
    # Wave 1 cost the only prefill; the decode-route admission added none.
    assert on.calls["prefill"] == 1
    assert on.stats()["prefix_cache"]["decode_route_admits"] == 1


# ---------------------------------------------------------------------------
# Compile pin: len(prompt_buckets) + len(suffix_buckets) + 1
# ---------------------------------------------------------------------------


def test_compile_count_pinned_with_suffix_buckets():
    # Suffix widths join the shared prefill executable set — same bodies,
    # more widths — so the pin is len(prompt_buckets) + len(suffix_
    # buckets) + 1 (decode), all compiled at warmup. No traffic shape
    # (cold, warm, decode-route, repeated hits) may add to it.
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    eng.warmup()
    expected = len(_CFG.prompt_buckets) + len(_CFG.suffix_buckets) + 1
    assert eng.num_compiles == expected
    waves = [_shared_prefix_prompts(5), _shared_prefix_prompts(5),
             _prompts((3, 9, 16), seed=8)]
    _run_waves(eng, waves, max_new=6)
    assert eng.num_compiles == expected
    assert eng.stats()["prefix_cache"]["hit_tokens"] > 0


def test_compile_count_pinned_with_speculation_on():
    # Speculation adds its verify executable on top: + 2 instead of + 1.
    model, params = _model_and_params("gpt2")
    cfg = dataclasses.replace(_CFG, speculation="ngram:3")
    eng = _engine(model, params, cfg)
    eng.warmup()
    expected = len(cfg.prompt_buckets) + len(cfg.suffix_buckets) + 2
    assert eng.num_compiles == expected
    _run_waves(eng, [_shared_prefix_prompts(4), _shared_prefix_prompts(4)],
               max_new=8)
    assert eng.num_compiles == expected


# ---------------------------------------------------------------------------
# Composition: speculation and sampling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_prefix_cache_composes_with_speculation(name):
    # Warm suffix-only admissions feed the same verify loop: spec-on
    # cache-on output must match the plain (spec-off cache-off) engine.
    model, params = _model_and_params(name)
    cfg = dataclasses.replace(_CFG, speculation="ngram:3")
    plain = dataclasses.replace(_CFG_OFF, speculation="off")
    waves = [_shared_prefix_prompts(4, seed=5), _shared_prefix_prompts(4, seed=5)]
    on = _engine(model, params, cfg)
    off = _engine(model, params, plain)
    assert _run_waves(on, waves) == _run_waves(off, waves)
    assert on.calls["verify"] > 0, "speculation never engaged"
    assert on.stats()["prefix_cache"]["hit_tokens"] > 0


def test_sampled_requests_sharing_a_prefix_are_legal():
    # The trie stores KV, not sampled tokens, and the rng chain is
    # fold_in(seed, request_id) on every admission path — so sampled
    # requests may share cached prefixes and still match the cache-off
    # engine exactly (same submission order -> same request ids).
    model, params = _model_and_params("gpt2")
    waves = [_shared_prefix_prompts(3, seed=21)] * 2
    on = _engine(model, params)
    off = _engine(model, params, _CFG_OFF)
    got_on = _run_waves(on, waves, max_new=8, temperature=0.8)
    got_off = _run_waves(off, waves, max_new=8, temperature=0.8)
    assert got_on == got_off
    assert on.stats()["prefix_cache"]["hit_tokens"] > 0


# ---------------------------------------------------------------------------
# Eviction pressure: parity survives a pool too small to keep the cache
# ---------------------------------------------------------------------------


def test_parity_under_eviction_pressure():
    # A deliberately tiny pool (7 usable blocks) swapped in under the
    # same device cache: the trie churns — publish, evict, re-publish —
    # and every admission that hits must still read valid KV. Output
    # stays identical to the cache-off engine throughout.
    model, params = _model_and_params("gpt2")
    on = _engine(model, params)
    # Subset of the device pool's blocks, so page-table rows stay valid.
    assert on.scheduler.pool.num_blocks > 8
    on.scheduler.pool = KVBlockPool(8, _CFG.block_size, prefix_cache=True)
    off = _engine(model, params, _CFG_OFF)
    waves = [_shared_prefix_prompts(3, seed=k) for k in (1, 2, 1, 2, 1)]
    assert _run_waves(on, waves, max_new=4) == _run_waves(off, waves,
                                                          max_new=4)
    pool = on.scheduler.pool
    assert pool.evictions > 0, "pressure never forced an eviction"
    assert pool.used_blocks == 0
    assert pool.used_blocks + pool.free_blocks + pool.cached_blocks == 7


# ---------------------------------------------------------------------------
# Replica probe + telemetry surface
# ---------------------------------------------------------------------------


def test_prefix_match_len_probe_is_read_only():
    # The router's affinity score: longest cached prefix in tokens,
    # without touching refcounts or LRU state.
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    prompts = _shared_prefix_prompts(2, seed=9)
    assert eng.prefix_match_len(prompts[0]) == 0
    _run_waves(eng, [prompts[:1]], max_new=5)
    hit = eng.prefix_match_len(prompts[1])
    assert hit == 8  # the shared prefix, in whole blocks
    before = eng.scheduler.pool.evictable_blocks
    for _ in range(5):
        eng.prefix_match_len(prompts[1])
    assert eng.scheduler.pool.evictable_blocks == before


def test_prefix_telemetry_surface(tmp_path):
    from distributeddeeplearning_tpu.telemetry import Telemetry

    model, params = _model_and_params("gpt2")
    tel = Telemetry(enabled=True, out_dir=str(tmp_path), ring_size=1 << 14)
    cfg = dataclasses.replace(_CFG, gauge_every=1)
    eng = _engine(model, params, cfg, telemetry=tel)
    _run_waves(eng, [_shared_prefix_prompts(3), _shared_prefix_prompts(3)],
               max_new=5)

    # Every admission event carries the tokens the trie absorbed; warm
    # wave entries are positive.
    admits = [e for e in eng.events if e.get("event") == "request_admitted"]
    assert admits and all("cached_tokens" in e for e in admits)
    assert any(e["cached_tokens"] > 0 for e in admits)
    # The cached_prefill_skip histogram saw one sample per admission —
    # cold zeros land in the underflow bucket, warm hits above it.
    h = tel.hists["cached_prefill_skip"]
    assert h.count == len(admits)
    # Counters + hit-rate gauge on the cadence output.
    gauge_recs = [e for e in eng.events
                  if e.get("event") == "serving_gauges"
                  and "prefix_hit_rate" in e]
    assert gauge_recs
    assert 0.0 < gauge_recs[-1]["prefix_hit_rate"] <= 1.0
    pc = eng.stats()["prefix_cache"]
    total_prompt = sum(
        len(s.request.prompt) for s in eng.scheduler.finished
    )
    assert pc["hit_tokens"] + pc["miss_tokens"] == total_prompt
