"""Command A+ family (``models/cohere2_moe.py``) against its plain reference
(``benchmarks/references/cohere2_moe.py``), at a test's size on the CPU: one
period of the layer pattern (three window layers, window 8, and a global
one), hidden 32, 8 query heads on 2 KV heads of 8, 16 experts top-4 with two
averaged shared ones, vocabulary 256. Nothing of the program is copied here:
the numbers to agree with are the reference's.

Everything runs at float32, where the program and the reference do the same
arithmetic in another order: 1e-5 on a logit (they agree to ~2e-7; logits
are of size ~0.7). What bfloat16 does to routed experts is
``tests/test_glm4_moe_lite.py``'s subject and the same layer's.
"""

import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import (
    Config, ModelConfig, ServingConfig,
)
from distributeddeeplearning_tpu.models import cohere2_moe as cohere
from distributeddeeplearning_tpu.models import transformer
from distributeddeeplearning_tpu.serving import (
    Request, ServingEngine, check_serving_composition,
)
from distributeddeeplearning_tpu.serving.scheduler import blocks_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    # by file, as the harness does: references/ is not a package
    path = os.path.join(REPO, "benchmarks", "references", "cohere2_moe.py")
    spec = importlib.util.spec_from_file_location("_ref_cohere2_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

TINY = dict(
    hidden_size=32, intermediate_size=24, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    num_experts=16, num_shared_experts=2, num_experts_per_tok=4,
    vocab_size=256, sliding_window=8,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    rope_theta=50000, layer_norm_eps=1e-5, max_position_embeddings=256,
    torch_dtype="float32",
)
SEED = 2**31 + 7
F32_TOL = 1e-5
WINDOW, BLOCK = 8, 4
RING = 3  # blocks of 4 that 8 consecutive positions can touch


def _share(first, count):
    """The configuration of a chip that holds ``count`` experts from
    ``first`` on, of the tiny model's 16."""
    return ref.dims({**TINY, "num_experts": count,
                     "published": {"num_experts": 16},
                     "held_first_expert": first})


def _params(d, seed=SEED):
    return jax.jit(
        lambda k: ref.program_tree(ref.weights_from_key(k, d), d)
    )(ref.seed_key(seed))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    d = ref.dims(TINY)
    return d, models.get_model("cohere2_moe", size="tiny"), _params(d)


@pytest.fixture(autouse=True)
def _full_precision_matmuls():
    # the CPU's float32 product is exact enough; this pins it
    with jax.default_matmul_precision("highest"):
        yield


def _engine(model, params, **kw):
    cfg = dict(slots=3, block_size=BLOCK, hbm_budget_mb=1, max_seq_len=64,
               prompt_buckets=(8, 16, 48))
    return ServingEngine(model, params, ServingConfig(**{**cfg, **kw}))


def _served_gap(state, w, d):
    """Widest gap by which a served token's logit lies below the best of
    the reference's full forward pass at its position."""
    seq = state.request.prompt + state.generated
    row = np.zeros((64,), np.int32)  # causal: padding changes nothing before
    row[:len(seq) - 1] = seq[:-1]
    lg = np.asarray(ref.logits(w, row, d))[:len(seq) - 1]
    lo = len(state.request.prompt) - 1
    picked = lg[np.arange(lo, len(seq) - 1), seq[lo + 1:]]
    return float((lg[lo:].max(-1) - picked).max())


# -- (a) the forward pass and the served path ------------------------------


@pytest.mark.parametrize("held", [None, (8, 4)], ids=["all", "a_share"])
def test_forward_matches_the_reference(tiny, held):
    # 48 tokens: six times the window, so the band cuts every window layer
    d, model, params = tiny
    if held:
        d = _share(*held)
        model, params = model.clone(held_experts=held), _params(d)
    toks = _tokens(48)
    out = model.apply({"params": params}, toks[None])
    want = ref.logits(ref.make_weights(SEED, d), toks, d)
    assert np.abs(np.asarray(out[0]) - np.asarray(want)).max() <= F32_TOL


def test_the_window_is_in_the_forward_pass(tiny):
    # a token 9 places back moves a global layer's output and no window
    # layer's: the model with its window widened past the sequence differs
    d, model, params = tiny
    toks = _tokens(24)
    near = model.apply({"params": params}, toks[None])
    wide = model.clone(window=64).apply({"params": params}, toks[None])
    assert np.abs(np.asarray(near - wide))[0, :8].max() == 0.0
    assert np.abs(np.asarray(near - wide))[0, 9:].max() > 1e-3


def test_engine_serves_what_the_reference_ranks_first(tiny):
    # Through submit/step with the engine's own scheduler, both pools and
    # programs: prompts of 3 (inside one block), 13 (a bucket of 16: pad in
    # the prompt's last block), 40 (five windows long in a bucket of 48:
    # prompt blocks that have left the window and pad blocks past the
    # prompt, both to the null block) and 9, on three lanes, so that lanes
    # at different depths decode in one batch beside an idle one and the
    # fourth request takes over a used ring; 30 new tokens turn a ring of 3
    # blocks more than twice. Every served token's logit within F32_TOL of
    # the reference's best at its position.
    d, model, params = tiny
    eng = _engine(model, params)
    states = [
        eng.submit(Request(prompt=_tokens(n, n).tolist(), max_new_tokens=k))
        for n, k in ((3, 10), (40, 20), (13, 30), (9, 5))
    ]
    eng.run()
    w = ref.make_weights(SEED, d)
    for st in states:
        assert len(st.generated) == st.request.max_new_tokens
        assert _served_gap(st, w, d) <= F32_TOL
    stats = eng.stats()
    assert stats["num_compiles"] == 4  # decode + one prefill a bucket
    # turns of the rings: 7 + 5 (the prompt of 40, then its 19 written
    # tokens), 1 + 7, 0 and 1
    assert stats["window"]["window_ring_wraps"] == 21
    # nothing is held once the lanes are empty, of either kind
    assert eng.scheduler.pool.used_blocks == 0
    assert eng.scheduler.window_pool.used_blocks == 0


# -- (b) the share adds up --------------------------------------------------


def test_four_shares_add_up_to_the_uncut_layer(tiny):
    # 16 experts in 4 shares of 4. Each share's block gives x + attention +
    # its experts' part + the shared experts; the four parts, with what
    # every chip computes alike counted once, are the uncut reference's
    # layer. The uncut side is the reference alone; the shares are the
    # program's, told which experts they hold.
    d, _, _ = tiny
    key = ref.seed_key(SEED)
    mm = ref._mm("f32")
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 32))
    lw = ref.layer_weights(key, 1, d)
    want, _ = ref.block(x, lw, d, True, mm)
    h = ref._ln(x, lw["norm"], d["eps"])
    alike = x + ref.attention(h, lw, d, True, mm) + ref.shared(h, lw, d, mm)
    total = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        ds = _share(first, 4)
        arch = cohere.Arch(
            num_heads=8, num_kv_heads=2, head_dim=8, embed_dim=32,
            expert_dim=24, num_routed_experts=16, held_experts=(first, 4),
            num_shared=2, shared_combine="average", top_k=4, window=WINDOW,
            rope_theta=50000.0, ln_eps=1e-5, dtype=jnp.float32,
            param_dtype=jnp.float32, attn_impl="xla", mesh=None,
            decode=False, kv_pages=None, window_blocks=0,
            paged_kernel="reference", kv_quant="off",
        )
        tree = ref._block_tree(ref.layer_weights(key, 1, ds), ds, jnp.float32)
        y = cohere.Cohere2Block(arch, WINDOW).apply({"params": tree}, x[None])
        total = total + (y[0] - alike)
    assert np.abs(np.asarray(alike + total - want)).max() <= F32_TOL
    # and the parts are parts: no share alone is the layer
    assert np.abs(np.asarray(y[0] - want)).max() > 1e-4


def test_a_pair_held_elsewhere_costs_no_group_and_drops_no_token():
    # The grouped product gets the held experts' counts alone: they sum to
    # the pairs routed here, whatever the batch shape; the router's view
    # (expert_load) still counts all 16.
    moe = cohere.RoutedExperts(
        16, 24, 4, 1.0, 2, decode=True, held=(4, 4),
        shared_combine="average", selection_bias=False,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32))
    variables = flax.core.meta.unbox(moe.init(jax.random.PRNGKey(1), x))
    assert "router_bias" not in variables["params"]
    assert variables["params"]["experts_gate"].shape == (4, 32, 24)
    assert variables["params"]["router"].shape == (32, 16)
    y, new = moe.apply(variables, x, mutable=["cache"])
    load = np.asarray(new["cache"]["expert_load"])
    assert load.shape == (16,) and load.sum() == 2 * 9 * 4
    # one token at a time gives the same rows: no capacity, no drop
    rows = [
        moe.apply(variables, x[b:b + 1, t:t + 1], mutable=["cache"])[0]
        for b in range(2) for t in range(9)
    ]
    assert np.abs(
        np.asarray(y).reshape(18, 32) - np.concatenate(rows).reshape(18, 32)
    ).max() <= 1e-6


def test_a_long_call_runs_the_experts_in_chunks_of_tokens(monkeypatch):
    from distributeddeeplearning_tpu.models import glm4_moe_lite as glm

    moe = cohere.RoutedExperts(16, 24, 4, 1.0, 2, held=(0, 8),
                               selection_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 30, 32))
    variables = flax.core.meta.unbox(moe.init(jax.random.PRNGKey(1), x))
    whole = moe.apply(variables, x)
    monkeypatch.setattr(glm, "_PAIR_CHUNK", 32)  # 120 pairs: 5 chunks of 6
    assert "scan" in str(jax.make_jaxpr(lambda v: moe.apply(v, x))(variables))
    assert np.abs(np.asarray(moe.apply(variables, x) - whole)).max() <= 1e-6


# -- (c) a window layer keeps its window and no more ------------------------


def test_a_window_layer_never_holds_more_than_its_window_of_a_lane(tiny):
    # One lane grows from 40 to 60 tokens. Counted from the pools at every
    # step: the global layer holds every block of the lane, a window layer
    # the ring (window + block_size tokens) and no more, which is also what
    # admission reserved; on the device only the ring's blocks and the null
    # block were ever written.
    d, model, params = tiny
    eng = _engine(model, params)
    short = eng.submit(Request(prompt=_tokens(5, 5).tolist(), max_new_tokens=3))
    long = eng.submit(Request(prompt=_tokens(40, 1).tolist(), max_new_tokens=20))
    eng.step()
    assert len(short.window_blocks) == blocks_for(5 + 3, BLOCK) == 2
    assert len(long.window_blocks) == RING
    assert len(long.blocks) == blocks_for(40 + 20, BLOCK)  # every token
    assert RING * BLOCK == WINDOW + BLOCK
    pool = eng.scheduler.window_pool
    while not long.done:
        tokens = len(long.request.prompt) + len(long.generated)
        row = eng._table_window[long.slot]
        mapped = row[row > 0]
        assert len(mapped) == len(set(mapped)) <= RING
        assert set(mapped) <= set(long.window_blocks)
        lanes = [s for s in eng.scheduler.active]
        assert pool.used_blocks == sum(len(s.window_blocks) for s in lanes)
        g = eng.scheduler.gauges()
        assert g["window_blocks_reserved"] == pool.used_blocks
        assert g["global_blocks_reserved"] == eng.scheduler.pool.used_blocks
        assert g["window_blocks_live"] <= RING * len(lanes)
        assert g["global_blocks_live"] >= blocks_for(tokens, BLOCK)
        eng.step()
    # token-layers held at the end: T in the global layer, the ring in each
    # window layer — not 4 T
    T = 60
    held = blocks_for(T, BLOCK) + 3 * RING
    assert held * BLOCK <= T + 3 * min(T, WINDOW + BLOCK)
    leaves = {
        path[-1].key: np.asarray(leaf) for path, leaf in
        jax.tree_util.tree_flatten_with_path(eng._cache["block_0"])[0]
    }
    written = {
        int(b) for b in np.nonzero(
            np.abs(leaves["pool_key_window"]).sum(axis=(1, 2))
        )[0]
    }
    # the two lanes' rings (2 + 3 blocks) and the null block, of 10
    assert len(written - {0}) <= 2 + RING
    assert leaves["pool_key_window"].shape[0] == eng.window_blocks == 10


def test_the_budget_buys_both_kinds_and_each_must_hold_a_request(tiny):
    d, model, params = tiny
    eng = _engine(model, params)
    # one global layer and three window layers of 2 x 16 floats a token
    assert eng.block_bytes == 2 * BLOCK * 16 * 4
    assert eng.window_block_bytes == 3 * eng.block_bytes
    assert eng.window_ring == RING
    # the window kind never needs more than slots x ring (+ its null block)
    assert eng.window_blocks == 1 + 3 * RING
    spent = (eng.num_blocks * eng.block_bytes
             + eng.window_blocks * eng.window_block_bytes)
    assert (1 << 20) - eng.block_bytes < spent <= 1 << 20
    assert eng.stats()["window"]["ring_blocks"] == RING
    assert eng.scheduler.pool.num_blocks == eng.num_blocks  # the global kind
    with pytest.raises(ValueError, match="KV blocks"):
        # 16 global blocks + 3 window blocks + two null blocks: 14,336 B
        ServingEngine(model, params, ServingConfig(
            slots=1, block_size=BLOCK, hbm_budget_mb=0, max_seq_len=64,
            prompt_buckets=(8,),
        ))


def test_gpt2_has_one_kind_of_layer_state():
    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(model, params, ServingConfig(
        slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
        prompt_buckets=(8,),
    ))
    assert eng.window_blocks == 0 and eng.scheduler.window_pool is None
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
    eng.run()
    assert not {
        k for k in {**eng.stats(), **eng.scheduler.gauges()}
        if k.startswith(("window", "global_"))
    }


# -- query chunks ------------------------------------------------------------


@pytest.mark.parametrize("L", [7, 16, 19])
def test_query_chunks_keep_what_is_left_over(L):
    # whole chunks, a remainder, and a call shorter than one chunk
    a = jnp.arange(2 * L * 3, dtype=jnp.float32).reshape(2, L, 3)
    pos = jnp.broadcast_to(jnp.arange(L), (2, L))
    fn = lambda x, p: x * 2 + p[..., None]  # noqa: E731
    out = transformer._in_query_chunks(fn, L, a, pos, chunk=8)
    assert np.array_equal(np.asarray(out), np.asarray(fn(a, pos)))


def test_the_query_chunk_follows_the_scores_size():
    assert transformer._query_chunk(12, 1024) == 512   # GPT-2
    assert transformer._query_chunk(20, 6144) == 512   # GLM-4.7-Flash
    assert transformer._query_chunk(128, 13312) == 128  # 0.87 GB of scores
    assert transformer.window_ring_blocks(4096, 16) == 257


# -- (e) what is fenced, by name --------------------------------------------


def _cfg(name, **serving):
    return Config(
        model=ModelConfig(name=name, kwargs={}),
        serving=ServingConfig(**serving),
    )


@pytest.mark.parametrize("serving,match", [
    ({"prefix_cache": True}, r"prefix_cache / suffix_buckets x window"),
    ({"prefix_cache": True, "spill_blocks": 4}, r"prefix_cache"),
    ({"role": "decode", "prefix_cache": True}, r"x window layers"),
    ({"kv_quant": "int8"}, r"kv_quant='int8' x window layers"),
    ({"speculation": "ngram:2"}, r"speculation='ngram:2' x window layers"),
    ({"attn_kernel": "pallas"}, r"attn_kernel='pallas' x window layers"),
])
def test_what_window_layers_lack_is_refused_by_name(tiny, serving, match):
    with pytest.raises(NotImplementedError, match=match):
        check_serving_composition(_cfg("cohere2_moe", **serving))
    check_serving_composition(_cfg("gpt2", **serving))  # one kind: built
    # and by the engine itself, for whoever builds one from a ServingConfig
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match="window layers"):
        _engine(model, params, **serving)


def test_the_family_is_servable_and_its_other_paths_say_what_they_lack():
    from distributeddeeplearning_tpu.serving.engine import SERVABLE_MODELS

    assert "cohere2_moe" in SERVABLE_MODELS
    check_serving_composition(_cfg("cohere2_moe"))
    model = models.get_model("cohere2_moe", size="tiny", decode=True)
    with pytest.raises(NotImplementedError, match="contiguous decode cache"):
        model.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    assert not hasattr(model, "num_experts")  # Trainer: no ep axis for it
    flash = models.get_model("cohere2_moe", size="tiny", attn_impl="flash")
    with pytest.raises(NotImplementedError, match="band mask"):
        flash.init(jax.random.PRNGKey(0), np.zeros((1, 16), np.int32))
    with pytest.raises(NotImplementedError, match="window layer x"):
        models.get_model(
            "cohere2_moe", size="tiny", decode=True, kv_pages=(4, 4, 4),
            kv_quant="int8",
        ).init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))


def test_a_multi_token_call_at_a_cursor_is_poisoned_not_wrong(tiny):
    # what the fences keep out, should it get in: L > 1 at a cursor
    _, model, params = tiny
    paged = model.clone(decode=True, kv_pages=(8, BLOCK, 4), window_blocks=8)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(
            paged.init, jax.random.PRNGKey(0), np.zeros((1, 1), np.int32)
        )["cache"],
    )

    def at(cursor):
        c = jax.tree_util.tree_map_with_path(
            lambda p, leaf: jnp.full_like(leaf, cursor)
            if p[-1].key == "seq_lens" else leaf, cache,
        )
        return paged.apply(
            {"params": params, "cache": c}, _tokens(4)[None],
            mutable=["cache"],
        )[0]

    assert np.isfinite(np.asarray(at(0))).all()
    assert np.isnan(np.asarray(at(2))).all()


# -- the server's entry -----------------------------------------------------


def test_cli_builds_and_serves_the_family():
    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = apply_overrides(
        load_config(os.path.join(REPO, "configs", "cohere2_moe.py")),
        ["model.kwargs.size='tiny'", "model.kwargs.held_experts=(0, 4)",
         "data.vocab_size=256", "data.seq_len=16", "train.seed=3",
         "serving.slots=2", "serving.block_size=4", "serving.max_seq_len=48",
         "serving.prompt_buckets=(16,)", "serving.hbm_budget_mb=1"],
    )
    check_serving_composition(cfg)
    _mesh, model, trainer, dataset = cli.build_all(cfg)
    model, state = cli.serving_model_and_state(cfg, model, trainer, dataset)
    assert not hasattr(state, "opt_state")
    kernel = state.params["block_1"]["moe"]["experts_gate"]
    assert kernel.dtype == jnp.bfloat16 and kernel.shape == (4, 32, 24)
    assert state.params["block_1"]["moe"]["router"].shape == (32, 16)
    assert state.params["norm"]["scale"].dtype == jnp.float32
    eng = ServingEngine(model, state.params, cfg.serving)
    st = eng.submit(Request(prompt=list(range(11)), max_new_tokens=6))
    eng.run()
    assert len(st.generated) == 6
    share = eng.stats()["moe_pairs_held_share"]
    assert 0.0 < share < 1.0  # 4 of 16 held: a quarter where routing is even
    assert eng.scheduler.gauges()["moe_pairs_held_share"] == share
