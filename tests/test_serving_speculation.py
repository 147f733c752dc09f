"""Speculative decoding on the serving engine (serving.speculation):
exact greedy token parity against the non-speculative engine and
generate(), the len(buckets)+2 compile-count pin, cursor rewind
round-trips (pool + page tables bit-identical to never having drafted),
acceptance clipping (max_new / EOS inside an accepted run), the
x-sampling submit fence, and the telemetry surface (spec_accept
histogram, decode-span accept args, accept-rate gauge, verify-exe
donation). The L>1 paged-attention lowering itself is exercised through
every verify call here — llama rows cover GQA (tiny = 4 heads over 2 kv
heads), staggered traffic covers mixed cursor depths, and partially
empty batches cover null-block idle lanes. Config-time fences live in
tests/test_composition_fences.py; pure-host drafter unit tests ride
along here (no device needed).
"""

import dataclasses

import numpy as np
import pytest

import jax

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import ServingConfig
from distributeddeeplearning_tpu.generate import generate, pad_prompts
from distributeddeeplearning_tpu.serving import (
    Request,
    ServingEngine,
    ngram_draft,
    speculation_k,
)

_K = 3
_CFG = ServingConfig(
    slots=3, block_size=4, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16), speculation=f"ngram:{_K}",
)
_CFG_OFF = dataclasses.replace(_CFG, speculation="off")


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
# Host drafter (pure Python, no device)
# ---------------------------------------------------------------------------


def test_ngram_draft_copies_after_most_recent_match():
    # Trailing bigram (2, 3) recurs twice; the MOST RECENT earlier
    # occurrence (index 4) wins, and its continuation is copied.
    assert ngram_draft([2, 3, 9, 8, 2, 3, 7, 6, 2, 3], 3) == [7, 6, 2]


def test_ngram_draft_prefers_longer_ngram():
    # Suffix (1, 2, 3) matches at the start -> continuation 50; the
    # shorter suffix (3,) alone would have matched index 6 -> 60.
    toks = [1, 2, 3, 50, 0, 0, 3, 60, 1, 2, 3]
    assert ngram_draft(toks, 1) == [50]


def test_ngram_draft_clips_to_k_and_to_history():
    toks = [5, 6, 7, 8, 5, 6]
    assert ngram_draft(toks, 1) == [7]          # clipped to k
    assert ngram_draft(toks, 10) == [7, 8, 5, 6]  # clipped to history end


def test_ngram_draft_prefers_full_window_match():
    # A greedy run of one repeated token: the most recent match of the
    # trailing n-gram sits ONE position back (continuation width 1), but
    # an earlier occurrence has k tokens before end-of-history — the
    # drafter must take the wide window, not the near one, or runs (the
    # most draftable streams) would only ever draft a single token.
    assert ngram_draft([7] * 10, 4) == [7, 7, 7, 7]
    # Non-degenerate version: trailing bigram (1, 2) recurs at s=6 with
    # only 2 tokens left and at s=0 with a full 3-token window; s=0 wins.
    assert ngram_draft([1, 2, 8, 9, 4, 0, 1, 2, 1, 2], 3) == [8, 9, 4]
    # But when BOTH windows are full, the most recent still wins.
    assert ngram_draft([1, 2, 8, 8, 1, 2, 9, 9, 1, 2], 2) == [9, 9]


def test_ngram_draft_empty_when_nothing_recurs():
    assert ngram_draft([1, 2, 3, 4, 5], 4) == []
    assert ngram_draft([9], 4) == []
    assert ngram_draft([], 4) == []


def test_ngram_draft_rejects_bad_k():
    with pytest.raises(ValueError, match="ngram_draft"):
        ngram_draft([1, 2, 1], 0)


def test_speculation_k_parse():
    assert speculation_k("off") == 0
    assert speculation_k("ngram:7") == 7
    for bad in ("ngram:", "ngram:x", "banana", "ngram:-2", "ngram:0"):
        with pytest.raises(ValueError, match="speculation"):
            speculation_k(bad)


# ---------------------------------------------------------------------------
# Exact greedy parity (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_speculative_greedy_matches_generate(name):
    # 5 requests over 3 lanes with mid-flight churn: lanes sit at mixed
    # cursor depths inside one verify batch, free lanes ride the null
    # block, and llama runs GQA through the L=K+1 paged lowering. Every
    # request's tokens must equal a plain generate() — speculation moves
    # WHEN tokens are produced, never WHICH.
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
    assert eng.calls["verify"] > 0, "speculation never engaged"
    assert eng.scheduler.stats()["used_blocks"] == 0
    for i, st in enumerate(done):
        assert st.generated == list(ref[i]), f"request {i}"


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_speculative_greedy_matches_full_forward_reference(name):
    # Same recipe as tests/test_generate_golden.py (seeds, shapes,
    # max_new=11) but decoded by the SPECULATIVE engine: every accepted
    # token must be the argmax of the no-cache full-forward reference
    # computed here on the same params — not whatever generate() currently
    # emits, so a bug that shifted both cached paths in lockstep still
    # fails.
    from distributeddeeplearning_tpu.generate import greedy_agreement

    model, params = _model_and_params(name)
    prompts = _prompts((5, 9, 3))
    eng = _engine(model, params)
    for p in prompts:
        eng.submit(Request(prompt=p, max_new_tokens=11))
    done = eng.run()
    assert eng.calls["verify"] > 0, "speculation never engaged"
    rec = greedy_agreement(
        model, params, prompts, [st.generated for st in done]
    )
    assert rec["tokens"] == 3 * 11
    assert rec["worst_logit_gap"] <= 1e-4, rec


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_speculative_matches_non_speculative_engine(name):
    # Same traffic through a spec-on and a spec-off engine: identical
    # token streams, and the spec-on engine needs FEWER device calls to
    # produce them (the whole point of the verify batch).
    model, params = _model_and_params(name)

    def run(cfg):
        eng = _engine(model, params, cfg)
        for i, p in enumerate(_prompts((4, 11, 6, 14), seed=9)):
            eng.submit(Request(prompt=p, max_new_tokens=9 + i))
        return [s.generated for s in eng.run()], eng

    toks_off, eng_off = run(_CFG_OFF)
    toks_on, eng_on = run(_CFG)
    assert toks_on == toks_off
    calls_on = eng_on.calls["decode"] + eng_on.calls["verify"]
    assert calls_on < eng_off.calls["decode"]
    spec = eng_on.stats()["speculation"]
    assert spec["k"] == _K
    assert 0.0 <= spec["accept_rate"] <= 1.0
    assert 1.0 <= spec["mean_accepted_per_step"] <= _K + 1


def test_compile_count_pinned_at_buckets_plus_two():
    # The AOT executable set with speculation on: one prefill per bucket
    # + decode + verify, compiled at warmup, and NO traffic shape —
    # bucket mix, draft/no-draft steps, churn — may add to it.
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    eng.warmup()
    expected = len(_CFG.prompt_buckets) + 2
    assert eng.num_compiles == expected
    for plen, new in [(3, 2), (8, 5), (9, 7), (16, 1), (1, 9), (12, 4)]:
        eng.submit(Request(prompt=_prompts((plen,))[0], max_new_tokens=new))
    eng.run()
    assert eng.num_compiles == expected
    assert eng.calls["verify"] > 0


# ---------------------------------------------------------------------------
# Cursor rewind: rejected drafts leave no trace
# ---------------------------------------------------------------------------


def _first_pool_leaf(eng):
    leaves = [
        leaf for path, leaf in
        jax.tree_util.tree_flatten_with_path(eng._cache)[0]
        if getattr(path[-1], "key", None) == "pool_key"
    ]
    return np.asarray(leaves[0])


def _valid_cells(eng):
    """(block, offset) pool cells holding LIVE KV (positions < cursor)
    for every active lane — the region rewind must keep bit-identical."""
    cells = []
    for s in eng.scheduler.active:
        for pos in range(int(eng._lens[s.slot])):
            blk = int(eng._table[s.slot, pos // eng.block_size])
            cells.append((blk, pos % eng.block_size))
    return cells


def test_draft_reject_redraft_leaves_state_bit_identical():
    # Force EVERY draft to be wrong (the hook knows the expected greedy
    # stream and proposes something else), so each step drafts K tokens,
    # writes their KV, rejects them all, rewinds, and redrafts — in
    # lockstep with a never-drafting engine. After every step: same
    # tokens, same host cursors and page tables, same pool free list,
    # and the pool's LIVE region bit-identical (rejected-position writes
    # are dead by construction; they sit past every cursor until real
    # tokens overwrite them).
    model, params = _model_and_params("gpt2")
    prompts = _prompts((5, 9, 3), seed=13)

    ref_eng = _engine(model, params, _CFG_OFF)
    exp = {}
    for i, p in enumerate(prompts):
        st = ref_eng.submit(Request(prompt=p, max_new_tokens=8))
        exp[st.request.request_id] = None
    for st in ref_eng.run():
        exp[st.request.request_id] = st.generated

    off = _engine(model, params, _CFG_OFF)
    on = _engine(model, params, _CFG)
    on._draft_for = lambda state: [
        (exp[state.request.request_id][len(state.generated)] + 1) % 97
    ] * _K
    for eng in (off, on):
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=8))
    busy_off = busy_on = True
    while busy_off or busy_on:
        busy_off, busy_on = off.step(), on.step()
        assert np.array_equal(off._lens, on._lens)
        # The spec engine's table is one draft window wider (the slack
        # columns that absorb overflowing draft writes); they must stay
        # parked on the null block, and the real columns must match.
        assert np.array_equal(off._table, on._table[:, :off.pages])
        assert (on._table[:, off.pages:] == 0).all()
        assert off.scheduler.pool._free == on.scheduler.pool._free
        cells = _valid_cells(on)
        if cells:
            a, b = _first_pool_leaf(off), _first_pool_leaf(on)
            blks, offs = zip(*cells)
            assert np.array_equal(a[blks, offs], b[blks, offs])
    assert on.calls["verify"] > 0
    spec = on.stats()["speculation"]
    assert spec["draft_hits"] == 0  # every draft rejected...
    assert spec["mean_accepted_per_step"] == 1.0  # ...one token per step
    for st in on.scheduler.finished:
        assert st.generated == exp[st.request.request_id]


def test_acceptance_clipped_at_max_new_tokens():
    # An oracle draft hook (always proposes the true continuation) would
    # overshoot max_new_tokens without the acceptance clip.
    model, params = _model_and_params("gpt2")
    prompt = _prompts((6,), seed=21)[0]
    ref_eng = _engine(model, params, _CFG_OFF)
    ref_eng.submit(Request(prompt=prompt, max_new_tokens=7))
    expected = ref_eng.run()[0].generated

    eng = _engine(model, params)
    eng._draft_for = lambda state: expected[
        len(state.generated):len(state.generated) + _K
    ] or [1] * _K
    st = eng.submit(Request(prompt=prompt, max_new_tokens=7))
    eng.run()
    assert st.generated == expected
    assert len(st.generated) == 7  # exactly max_new, never past it
    assert eng.stats()["speculation"]["accept_rate"] > 0.5


def test_eos_inside_accepted_run_ends_request_there():
    # Pick the 3rd greedy token as eos_id: with an oracle draft the eos
    # arrives INSIDE an accepted run and must cut it exactly where the
    # one-token loop would have stopped.
    model, params = _model_and_params("gpt2")
    prompt = _prompts((5,), seed=33)[0]
    ref_eng = _engine(model, params, _CFG_OFF)
    ref_eng.submit(Request(prompt=prompt, max_new_tokens=12))
    expected = ref_eng.run()[0].generated
    eos = expected[2]
    cut = expected[:expected.index(eos) + 1]

    cfg = dataclasses.replace(_CFG, eos_id=eos)
    eng = _engine(model, params, cfg)
    eng._draft_for = lambda state: expected[
        len(state.generated):len(state.generated) + _K
    ] or [1] * _K
    st = eng.submit(Request(prompt=prompt, max_new_tokens=12))
    eng.run()
    assert st.generated == cut
    assert eng.scheduler.stats()["used_blocks"] == 0


def test_submit_fences_sampled_requests():
    model, params = _model_and_params("gpt2")
    eng = _engine(model, params)
    with pytest.raises(NotImplementedError, match="speculation"):
        eng.submit(Request(
            prompt=[1, 2, 3], max_new_tokens=4, temperature=0.8,
        ))
    # greedy requests pass, and the engine still works afterwards
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert len(eng.run()) == 1


# ---------------------------------------------------------------------------
# Telemetry surface
# ---------------------------------------------------------------------------


def test_speculation_telemetry_surface(tmp_path):
    from distributeddeeplearning_tpu.telemetry import (
        SPEC_ACCEPT_HIST, Telemetry,
    )

    model, params = _model_and_params("gpt2")
    tel = Telemetry(enabled=True, out_dir=str(tmp_path), ring_size=1 << 14)
    cfg = dataclasses.replace(_CFG, gauge_every=1)
    eng = _engine(model, params, cfg, telemetry=tel)
    eng.warmup()
    for p in _prompts((5, 9, 3), seed=2):
        eng.submit(Request(prompt=p, max_new_tokens=9))
    eng.run()
    assert eng.calls["verify"] > 0

    # Accept-count histogram: one sample per (lane, verify step), values
    # in [1, K+1], and it rides stats_dict() into the fleet merge path.
    h = tel.hists[SPEC_ACCEPT_HIST]
    assert h.count == eng.spec["lane_steps"]
    s = h.summary()
    assert 1.0 <= s["mean_s"] <= _K + 1  # value is a COUNT, not seconds
    assert SPEC_ACCEPT_HIST in tel.stats_dict()["histograms"]

    # Decode spans on verify steps carry the accepted-length args.
    spec_spans = [
        sp for sp in tel.tracer.spans
        if sp.name == "decode" and sp.args.get("speculative")
    ]
    assert spec_spans
    assert all("accepted" in sp.args and "draft_hits" in sp.args
               for sp in spec_spans)
    assert sum(sp.args["accepted"] for sp in spec_spans) \
        == eng.spec["emitted"]

    # Gauge cadence output includes the running accept rate.
    gauge_recs = [e for e in eng.events
                  if e.get("event") == "serving_gauges"
                  and "spec_accept_rate" in e]
    assert gauge_recs
    assert 0.0 <= gauge_recs[-1]["spec_accept_rate"] <= 1.0

    # The verify executable donates its cache like decode (in-place pool).
    assert tel.registry.get("serving_verify")["donated_args"] > 0
    assert tel.registry.get("serving_verify")["recompiles"] == 0
