"""KV-cache memory hierarchy (serving.spill_blocks): the host spill tier
behind the prefix trie.

Pool layer (pure Python): eviction demotes refcount-0 blocks to
negative-id host nodes instead of destroying them, the spilled ledger is
capped with its own LRU (second eviction is final), spill callbacks are
coalesced per eviction batch and fire before any freed block can be
reused, matching/probing walks through both tiers, promotion re-keys
host nodes onto fresh device blocks, and a completion publish that hits
a spilled hash ADOPTS the publisher's device copy (a free promotion).

Engine layer: exact greedy warm-vs-cold parity for spill_codec='fp'
(incl. under spill-cap pressure and composed with speculation), the
unchanged compile pin with zero steady-state recompiles, the int8 codec
logit-tolerance bar and its adversarial random-trace control, spill
telemetry (stats keys, promote_wait histogram), and the constrain_pool
bench hook's guards.

Three-tier soak + gauges live in tests/test_serving_units.py; config
fences in tests/test_composition_fences.py.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import ServingConfig
from distributeddeeplearning_tpu.generate import logits_at, prefill
from distributeddeeplearning_tpu.serving import (
    KVBlockPool,
    Request,
    ServingEngine,
    chain_digests,
)

_CFG = ServingConfig(
    slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16), prefix_cache=True, suffix_buckets=(4,),
    spill_blocks=12,
)
_CFG_OFF = dataclasses.replace(_CFG, spill_blocks=0)


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


def _model_and_params(name="gpt2", seed=7):
    model = models.get_model(name, size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(seed), np.zeros((1, 8), np.int32)
    )["params"]
    return model, params


def _engine(model, params, cfg=_CFG, **kw):
    return ServingEngine(model, params, cfg, clock=_fake_clock(), **kw)


def _store_pool(num_blocks=8, block_size=4, spill_blocks=4, **kw):
    """Pool wired to a strict dict store (the engine mimic): spill_fn
    records batches, drop_fn pops (KeyError = contract violation)."""
    store: dict[bytes, int] = {}
    batches: list[list] = []

    def spill_fn(pairs):
        batches.append(list(pairs))
        store.update({h: b for b, h in pairs})

    pool = KVBlockPool(num_blocks, block_size, prefix_cache=True,
                       spill_blocks=spill_blocks, spill_fn=spill_fn,
                       drop_fn=store.pop, **kw)
    return pool, store, batches


def _seed_chain(pool, tokens, *, refs=0):
    n = len(tokens) // pool.block_size
    blocks = pool.alloc(n)
    assert blocks is not None
    pool.publish(tokens[:n * pool.block_size], blocks, refs=refs)
    return blocks


def _alternating_waves(seed=3):
    """Waves alternating two 12-token prefixes so the off-duty prefix
    keeps getting evicted on a constrained pool: A, B, A, B, A."""
    rng = np.random.default_rng(seed)
    pa = list(map(int, rng.integers(1, 97, 12)))
    pb = list(map(int, rng.integers(1, 97, 12)))
    waves = []
    for w, prefix in enumerate((pa, pb, pa, pb, pa)):
        waves.append([
            prefix + list(map(int, rng.integers(1, 97, 2 + (w + j) % 3)))
            for j in range(2)
        ])
    return waves


def _run_waves(eng, waves, max_new=6):
    out = []
    for wave in waves:
        for p in wave:
            eng.submit(Request(prompt=list(p), max_new_tokens=max_new))
        out.append([s.generated for s in eng.run()])
    return out


# ---------------------------------------------------------------------------
# Pool: spill mechanics, ledger cap, callbacks
# ---------------------------------------------------------------------------


def test_pool_spill_fences():
    with pytest.raises(ValueError, match="spill_blocks"):
        KVBlockPool(8, 4, prefix_cache=True, spill_blocks=-1)
    with pytest.raises(ValueError, match="prefix_cache"):
        KVBlockPool(8, 4, spill_blocks=2)


def test_eviction_spills_then_final_evicts_at_cap():
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=2)
    a = _seed_chain(pool, [1] * 4)   # tick 1 (LRU)
    b = _seed_chain(pool, [2] * 4)   # tick 2
    c = _seed_chain(pool, [3] * 4)   # tick 3
    assert (a, b, c) == ([1], [2], [3])
    # 4 free + 3 evictable; alloc 6 forces two spills: a then b (LRU
    # order), both surviving as host nodes within the budget.
    got = pool.alloc(6)
    assert pool.spilled_blocks == 2 == len(store)
    assert pool.match([1] * 4 + [0]) == [-1]   # a spilled first
    assert pool.match([2] * 4 + [0]) == [-2]
    assert pool.spills == 2 and pool.final_evictions == 0
    # Device conservation never counts the host ledger.
    assert pool.used_blocks + pool.free_blocks + pool.cached_blocks == 7
    pool.free(got)
    # One more squeeze: c spills, but the ledger is at cap — the LRU
    # host node (a, spilled earliest) is final-evicted first.
    got = pool.alloc(7)
    assert pool.spilled_blocks == 2
    assert pool.final_evictions == 1
    assert pool.match([1] * 4 + [0]) == []     # a is gone for good
    assert pool.match([3] * 4 + [0]) == [-3]
    assert set(store) == {
        nd.chain_hash for i, nd in pool._cached.items() if i < 0
    }


def test_spill_batch_is_coalesced_per_alloc():
    pool, _, batches = _store_pool(num_blocks=8, spill_blocks=4)
    _seed_chain(pool, [1] * 4)
    _seed_chain(pool, [2] * 4)
    _seed_chain(pool, [3] * 4)
    pool.alloc(7)  # three evictions inside ONE alloc
    assert len(batches) == 1 and len(batches[0]) == 3
    # The batch names the victims' (block, hash) pairs in eviction order,
    # BEFORE any of those blocks were handed out — the engine's capture
    # window.
    assert [b for b, _ in batches[0]] == [1, 2, 3]


def test_final_eviction_cancels_pending_capture_same_alloc():
    # A node spilled and final-evicted within the SAME alloc batch: its
    # KV capture is still pending when the cap bites, so the pool must
    # cancel the batch entry rather than call drop_fn for a payload that
    # does not exist yet (the strict store mimic would KeyError, and the
    # deferred capture would then leak a stale orphan payload).
    pool, store, batches = _store_pool(num_blocks=8, spill_blocks=1)
    _seed_chain(pool, [1] * 4)
    _seed_chain(pool, [2] * 4)
    _seed_chain(pool, [3] * 4)
    pool.alloc(7)  # spill a; spill b final-evicts a; spill c final-evicts b
    assert pool.spilled_blocks == 1 == len(store)
    assert pool.final_evictions == 2
    # Only the surviving node's capture ran.
    assert [h for _, h in batches[0]] == list(store)
    assert pool.match([3] * 4 + [0]) != []


def test_acquired_host_node_survives_final_eviction_pressure():
    # admit() acquires the matched chain (host nodes included) BEFORE
    # alloc, so a refcount>0 host node must never be final-evicted by
    # the very allocation that is about to promote it.
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=1)
    a = _seed_chain(pool, [1] * 4)
    got = pool.alloc(7)          # a spills to -1 (ledger now full)
    pool.free(got)
    hit = pool.match([1] * 4 + [0])
    assert hit == [-1]
    pool.acquire(hit)            # pin, as admission does
    _seed_chain(pool, [2] * 4)
    got = pool.alloc(7)          # pressure: b must DROP (no evictable host)
    assert pool.match([1] * 4 + [0]) == [-1], "pinned host node evicted"
    assert pool.match([2] * 4 + [0]) == []
    pool.free(got)
    # Promote the pinned node and make sure the chain comes back whole.
    blocks = pool.alloc(1)
    pairs = pool.promote([-1], blocks)
    assert [b for b, _ in pairs] == blocks
    assert pool.match([1] * 4 + [0]) == blocks
    assert pool._cached[blocks[0]].refs == 1
    assert pool.spilled_blocks == 0
    (nd,) = [pool._cached[b] for b in blocks]
    assert a != blocks or nd.chain_hash  # id may differ; hash is identity


def test_match_and_digest_probe_through_host_tier():
    pool, _, _ = _store_pool(num_blocks=8, spill_blocks=4)
    toks = list(range(1, 13))
    _seed_chain(pool, toks)
    got = pool.alloc(7)  # all three blocks spill
    pool.free(got)
    assert pool.spilled_blocks == 3
    m = pool.match(toks + [99])
    assert len(m) == 3 and all(i < 0 for i in m)
    digests = chain_digests(toks + [99], 4)
    assert pool.match_digests(digests) == 3
    # Partial chains and misses behave exactly like the device tier.
    assert pool.match_digests(chain_digests(toks[:8] + [0], 4)) == 2
    assert pool.match_digests(chain_digests([55] + toks, 4)) == 0
    assert pool.match_len(toks + [99]) == 12


def test_promote_rekeys_parent_child_links():
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=4)
    toks = list(range(1, 13))
    _seed_chain(pool, toks)
    got = pool.alloc(7)
    pool.free(got)
    chain = pool.match(toks + [99])       # [-1, -2, -3] leaf-first spill
    pool.acquire(chain)
    blocks = pool.alloc(3)
    pairs = pool.promote(chain, blocks)
    assert [b for b, _ in pairs] == blocks
    # Chain is device again, root->leaf parent links re-keyed.
    assert pool.match(toks + [99]) == blocks
    nd0, nd1, nd2 = (pool._cached[b] for b in blocks)
    assert nd0.parent is None and nd1.parent == blocks[0]
    assert nd2.parent == blocks[1]
    assert nd0.children == {blocks[1]} and nd1.children == {blocks[2]}
    assert pool.promotes == 3 and pool.spilled_blocks == 0
    with pytest.raises(ValueError, match="device-tier"):
        pool.promote([blocks[0]], [blocks[1]])


def test_publish_adoption_recovers_host_node_without_upload():
    # A completing request re-publishes its written blocks; when a chain
    # hash now lives on the HOST tier, the publisher's own device copy is
    # adopted in place — promotion without a host->device transfer — and
    # the host payload is dropped.
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=4)
    toks = [7] * 8
    _seed_chain(pool, toks)
    got = pool.alloc(7)      # both blocks spill
    assert pool.spilled_blocks == 2 and len(store) == 2
    # Another request owning freshly-written copies of the same content
    # publishes: both host nodes adopt, the store empties via drop_fn.
    pub, trav = pool.publish(toks, got[:2], refs=0)
    assert pub == got[:2] and trav == []
    assert pool.adoptions == 2 and pool.spilled_blocks == 0
    assert not store
    assert pool.match(toks + [0]) == got[:2]
    pool.free(got[2:])
    assert pool.used_blocks == 0 and pool.free_blocks == 5


def test_flush_drops_both_tiers_via_drop_fn():
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=4)
    _seed_chain(pool, [1] * 8)
    got = pool.alloc(7)      # spill both
    pool.free(got)
    _seed_chain(pool, [2] * 4)
    assert pool.spilled_blocks == 2 and pool.cached_blocks == 1
    n = pool.flush_cache()
    assert n == 3
    assert pool.cached_blocks == 0 and pool.spilled_blocks == 0
    assert not store
    assert pool.free_blocks == 7


def test_spill_promote_respill_lru_is_deterministic():
    # Satellite: the full spill -> promote -> re-spill cycle under the
    # logical clock, with tie-breaks pinned — same-tick host nodes
    # final-evict earliest-spilled first, and the earliest-spilled of a
    # same-tick device pair is the lower block id.
    pool, store, _ = _store_pool(num_blocks=8, spill_blocks=2)
    d = _seed_chain(pool, [1] * 4)
    e = _seed_chain(pool, [2] * 4)
    pool.acquire(d + e)      # ONE shared tick: d and e tie on last_use
    pool.release(d + e)
    got = pool.alloc(7)      # both spill; d (lower id) first -> -1
    assert pool.match([1] * 4 + [0]) == [-1]
    assert pool.match([2] * 4 + [0]) == [-2]
    pool.free(got)
    # Promote e (touches it), then re-spill: e goes back to host with a
    # FRESH id and a newer tick.
    hit = pool.match([2] * 4 + [0])
    pool.acquire(hit)
    blocks = pool.alloc(1)
    pool.promote(hit, blocks)
    pool.release(blocks)
    got = pool.alloc(7)      # e re-spills -> -3
    assert pool.match([2] * 4 + [0]) == [-3]
    pool.free(got)
    # Cap pressure: d and e's host ticks differ now (promote touched e),
    # so d — older AND earliest-spilled — is final-evicted first.
    _seed_chain(pool, [3] * 4)
    got = pool.alloc(7)
    assert pool.match([1] * 4 + [0]) == []
    assert pool.match([2] * 4 + [0]) == [-3]
    assert pool.final_evictions == 1
    assert len(store) == pool.spilled_blocks == 2
    pool.free(got)


def test_scheduler_admit_promotes_and_counts_host_hits():
    # Scheduler-level promotion: a warm admission whose chain crosses
    # into the host tier allocates device blocks for the host suffix of
    # the chain, promotes, and reports (block, hash) pairs on
    # state.promoted for the engine's upload.
    from distributeddeeplearning_tpu.serving import Scheduler

    store: dict[bytes, int] = {}
    pool = KVBlockPool(16, 4, prefix_cache=True, spill_blocks=8,
                       spill_fn=lambda ps: store.update(
                           {h: b for b, h in ps}),
                       drop_fn=store.pop)
    s = Scheduler(2, pool, 32)
    toks = list(range(1, 13))
    _seed_chain(pool, toks)
    got = pool.alloc(15)     # spill all three blocks
    pool.free(got)
    assert pool.spilled_blocks == 3

    def bucket_of(n):
        return 16

    s.submit(Request(prompt=toks + [40, 41], max_new_tokens=4), now=0.0)
    (st,) = s.admit(0.0, bucket_of, suffix_bucket_of=lambda n: 4,
                    cover_tokens=32)
    assert len(st.promoted) == 3
    assert [h for _, h in st.promoted] == chain_digests(toks + [0], 4)
    assert st.cached_len == 12 and all(b > 0 for b in st.cached_blocks)
    assert not st.decode_route
    assert s.prefix_hit_tokens_host == 12
    assert s.stats()["prefix_cache"]["hit_tokens_host"] == 12
    # Full-prefix hit through the host tier rides the decode route.
    for _, h in st.promoted:
        store.pop(h)
    st.promoted = []
    st.generated = [1]
    s.complete(st.slot, now=1.0)
    got = pool.alloc(pool.free_blocks + pool.evictable_blocks)
    pool.free(got)           # re-spill everything refcount-0
    s.submit(Request(prompt=toks + [99], max_new_tokens=4), now=2.0)
    (st2,) = s.admit(2.0, bucket_of, suffix_bucket_of=lambda n: 4,
                     cover_tokens=32)
    assert st2.decode_route and st2.promoted


# ---------------------------------------------------------------------------
# Engine: fp parity, compile pin, codec bars, telemetry
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt2():
    return _model_and_params("gpt2")


def test_warm_cold_parity_with_fp_spill(gpt2):
    # Alternating-prefix waves on a device pool too small for both
    # working sets: the off-duty prefix keeps spilling, re-admissions
    # promote it back, and the token streams must equal the spill-off
    # engine's exactly (fp payloads are bitwise).
    model, params = gpt2
    waves = _alternating_waves()
    on = _engine(model, params)
    off = _engine(model, params, _CFG_OFF)
    on.warmup(), off.warmup()
    on.constrain_pool(14), off.constrain_pool(14)
    assert _run_waves(on, waves) == _run_waves(off, waves)
    pc = on.stats()["prefix_cache"]
    assert pc["spills"] > 0 and pc["promotes"] > 0
    assert pc["hit_tokens_host"] > 0
    assert pc["hit_tokens_host"] + pc["hit_tokens_device"] \
        == pc["hit_tokens"]
    # Store and ledger agree after real engine traffic too.
    assert pc["spill_store_blocks"] == pc["spilled_blocks"]


def test_parity_holds_under_spill_cap_pressure(gpt2):
    # A 2-block host budget forces final evictions mid-trace; dropped
    # prefixes simply go cold again — tokens must not move.
    model, params = gpt2
    waves = _alternating_waves(seed=5)
    tight = _engine(model, params,
                    dataclasses.replace(_CFG, spill_blocks=2))
    off = _engine(model, params, _CFG_OFF)
    tight.warmup(), off.warmup()
    tight.constrain_pool(14), off.constrain_pool(14)
    assert _run_waves(tight, waves) == _run_waves(off, waves)
    pc = tight.stats()["prefix_cache"]
    assert pc["final_evictions"] > 0, "the cap never bit"
    assert pc["spilled_blocks"] <= 2


def test_spill_composes_with_speculation(gpt2):
    model, params = gpt2
    cfg = dataclasses.replace(_CFG, speculation="ngram:3")
    waves = _alternating_waves(seed=9)
    on = _engine(model, params, cfg)
    off = _engine(model, params,
                  dataclasses.replace(_CFG_OFF, speculation="ngram:3"))
    on.warmup(), off.warmup()
    on.constrain_pool(14), off.constrain_pool(14)
    assert _run_waves(on, waves) == _run_waves(off, waves)
    # Speculation adds exactly the verify program to the pin.
    assert on.num_compiles == len(_CFG.prompt_buckets) \
        + len(_CFG.suffix_buckets) + 2


def test_compile_pin_unchanged_zero_steady_state_recompiles(gpt2):
    # The whole hierarchy is host bookkeeping + eager transfers: after
    # warmup, spill/promote/final-evict traffic compiles NOTHING.
    model, params = gpt2
    eng = _engine(model, params)
    eng.warmup()
    pin = len(_CFG.prompt_buckets) + len(_CFG.suffix_buckets) + 1
    assert eng.num_compiles == pin
    eng.constrain_pool(14)
    _run_waves(eng, _alternating_waves())
    pc = eng.stats()["prefix_cache"]
    assert pc["spills"] > 0 and pc["promotes"] > 0
    assert eng.num_compiles == pin, "spill path triggered a recompile"


def _warm_suffix_logits(model, params, codec):
    """Seed a prefix, force it to spill, re-admit warm (promote), and
    return the suffix prefill's last-position logits — eager, straight
    through the engine's own cache, so the only delta between codecs is
    the promoted KV bytes."""
    cfg = dataclasses.replace(_CFG, spill_codec=codec)
    eng = _engine(model, params, cfg)
    eng.warmup()
    eng.constrain_pool(14)
    rng = np.random.default_rng(13)
    prefix = list(map(int, rng.integers(1, 97, 12)))
    eng.submit(Request(prompt=prefix + [50, 51], max_new_tokens=2))
    eng.run()
    pool = eng.scheduler.pool
    got = pool.alloc(pool.free_blocks + pool.evictable_blocks)
    pool.free(got)
    assert pool.spilled_blocks >= 3
    eng.submit(Request(prompt=prefix + [60, 61], max_new_tokens=2))
    (st,) = eng.scheduler.admit(
        0.0, eng.bucket_of, suffix_bucket_of=eng.suffix_bucket_of,
        cover_tokens=eng.pages * eng.block_size,
    )
    assert st.promoted, "warm admission did not cross the host tier"
    eng._start_promotions(st)
    eng._apply_promotions(st)
    row = np.zeros((eng.pages,), np.int32)
    chain = st.cached_blocks + st.blocks
    row[:len(chain)] = chain
    suffix = st.request.prompt[st.cached_len:]
    tokens = np.zeros((1, st.bucket), np.int32)
    tokens[0, :len(suffix)] = suffix
    cache1 = eng._inject(eng._cache, row[None], np.int32([st.cached_len]))
    out, _ = prefill(eng.model, eng._dequant(eng._params), cache1,
                     jnp.asarray(tokens))
    return np.asarray(
        logits_at(out, jnp.asarray(np.int32([len(suffix) - 1]))),
        np.float32,
    )


def test_int8_promote_within_logit_tolerance(gpt2):
    # The codec bar: int8-promoted KV may move the next-token logits by
    # at most 5% of the fp logits' dynamic range. fp is the bitwise
    # reference.
    model, params = gpt2
    ref = _warm_suffix_logits(model, params, "fp")
    quant = _warm_suffix_logits(model, params, "int8")
    scale = float(np.abs(ref).max())
    drift = float(np.abs(ref - quant).max())
    assert drift <= 0.05 * scale, (drift, scale)


def test_int8_adversarial_random_trace_hit_rate_zero(gpt2):
    # The honesty control, PR-15 style: unique random prompts share no
    # prefixes, so an int8-spill engine must report hit_rate == 0.0
    # exactly — the codec cannot manufacture hits, and nothing promoted
    # means nothing quantized touches any request's logits.
    model, params = gpt2
    eng = _engine(model, params,
                  dataclasses.replace(_CFG, spill_codec="int8"))
    eng.warmup()
    eng.constrain_pool(14)
    rng = np.random.default_rng(23)
    waves = [
        [list(map(int, rng.integers(1, 97, 13 + j))) for j in range(2)]
        for _ in range(3)
    ]
    _run_waves(eng, waves)
    pc = eng.stats()["prefix_cache"]
    assert pc["hit_rate"] == 0.0
    assert pc["hit_tokens"] == 0 and pc["promotes"] == 0


def test_spill_stats_keys_and_promote_wait_histogram(gpt2, tmp_path):
    from distributeddeeplearning_tpu.telemetry import Telemetry

    model, params = gpt2
    tel = Telemetry(enabled=True, out_dir=str(tmp_path))
    eng = _engine(model, params, telemetry=tel)
    eng.warmup()
    eng.constrain_pool(14)
    _run_waves(eng, _alternating_waves())
    pc = eng.stats()["prefix_cache"]
    for key in ("spill_codec", "spill_store_blocks", "spill_bytes",
                "promote_bytes", "spill_transfers", "promote_transfers",
                "spill_budget", "spilled_blocks", "spills", "promotes",
                "adoptions", "final_evictions"):
        assert key in pc, key
    assert pc["spill_codec"] == "fp"
    assert pc["spill_bytes"] > 0 and pc["promote_bytes"] > 0
    assert pc["spill_transfers"] > 0 and pc["promote_transfers"] > 0
    # promote_wait flows through the PR 12 histogram machinery (fleet
    # mergeable), one sample per promoting admission.
    h = tel.hists.get("promote_wait")
    assert h is not None and h.count == pc["promote_transfers"]
    # A spill-off engine reports none of this.
    off = _engine(model, params, _CFG_OFF)
    assert "spill_bytes" not in off.stats()["prefix_cache"]


def test_constrain_pool_guards(gpt2):
    model, params = gpt2
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="constrain_pool"):
        eng.constrain_pool(eng.num_blocks + 1)
    with pytest.raises(ValueError, match="constrain_pool"):
        eng.constrain_pool(1)
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    with pytest.raises(RuntimeError, match="in flight"):
        eng.constrain_pool(8)


# ---------------------------------------------------------------------------
# Engine: spill-store persistence (save_spill_store / load_spill_store)
# ---------------------------------------------------------------------------


def _spill_then_save(model, params, shared, tail, path, *, cfg=_CFG):
    """Run the shared prompt, churn the constrained pool until its chain
    lives on the host tier, then persist the store."""
    rng = np.random.default_rng(5)
    eng = _engine(model, params, cfg)
    eng.constrain_pool(8)
    eng.submit(Request(prompt=shared + tail, max_new_tokens=8))
    eng.run()
    for _ in range(3):  # unrelated traffic squeezes the chain out
        p = list(map(int, rng.integers(1, 97, 15)))
        eng.submit(Request(prompt=p, max_new_tokens=8))
        eng.run()
    st = eng.stats()["prefix_cache"]
    assert st["spilled_blocks"] > 0
    n = eng.save_spill_store(path)
    assert n == st["spill_store_blocks"]
    return eng


@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_spill_store_round_trip_parity_vs_never_restarted(
    kv_quant, tmp_path
):
    # A restarted engine that loads the persisted host tier must serve
    # the old traffic's prefix FROM that tier (real promotes, not a
    # re-prefill that happens to agree) and emit exactly what a
    # never-restarted engine emits — for the fp pool bitwise, and for
    # the int8 pool because spilled payloads are already-quantized bytes
    # that ride through the fp codec unchanged.
    cfg = dataclasses.replace(_CFG, kv_quant=kv_quant)
    model, params = _model_and_params()
    rng = np.random.default_rng(4)
    shared = list(map(int, rng.integers(1, 97, 12)))
    tail = list(map(int, rng.integers(1, 97, 3)))
    path = str(tmp_path / "store.pkl")
    _spill_then_save(model, params, shared, tail, path, cfg=cfg)

    restarted = _engine(model, params, cfg)
    restarted.constrain_pool(8)
    assert restarted.load_spill_store(path) > 0
    restarted.submit(Request(prompt=shared + tail, max_new_tokens=8))
    (done_r,) = restarted.run()
    # The hit really came from the restored host tier.
    assert restarted.stats()["prefix_cache"]["promotes"] > 0
    assert restarted.scheduler.prefix_hit_tokens_host > 0

    cold = _engine(model, params, cfg)
    cold.constrain_pool(8)
    cold.submit(Request(prompt=shared + tail, max_new_tokens=8))
    (done_c,) = cold.run()
    assert done_r.generated == done_c.generated


def test_spill_store_load_rejects_layout_mismatch(tmp_path):
    # A store saved under kv_quant='int8' holds int8+scale pool rows; a
    # kv_quant='off' engine scattering them would corrupt the pool. The
    # loader fails by name instead.
    model, params = _model_and_params()
    rng = np.random.default_rng(4)
    shared = list(map(int, rng.integers(1, 97, 12)))
    tail = list(map(int, rng.integers(1, 97, 3)))
    cfg = dataclasses.replace(_CFG, kv_quant="int8")
    path = str(tmp_path / "store.pkl")
    _spill_then_save(model, params, shared, tail, path, cfg=cfg)
    plain = _engine(model, params, _CFG)
    with pytest.raises(ValueError, match="layout"):
        plain.load_spill_store(path)
    assert len(plain._spill_store) == 0  # nothing partially installed
