"""GLM-4.7-Flash family (``models/glm4_moe_lite.py``) against its plain
reference (``benchmarks/references/glm4_moe_lite.py``), at a test's size on
the CPU: 1 dense + 2 expert layers, hidden 64, 4 heads, ranks 24/16,
nope/rope/v 12/4/16, 8 experts top-2, vocabulary 256. Nothing of the
program is copied here: the numbers to agree with are the reference's.

Tolerances, on the widest gap of a row of logits (a position; logits are
of size ~0.65). The program at float32 compute and the reference do the
same float32 arithmetic in another order: 1e-5 (they agree to ~2e-7). At
bfloat16 compute every product's operands are rounded to 8 bits of
mantissa through 3 blocks: over 9 seeds a row moves by 0.0024 at the median
and 0.0039 at the most — but for the one row in a few hundred whose last
chosen expert and the next lie within that rounding of each other, which
then routes differently from the reference (the stated precision at work,
ISSUE 26; the router itself is float32 on both sides, its input is not) and
moves by 0.04-0.05 (2 of 9 seeds had one such row in 48). So two limits:
nine rows in ten within 0.008, every row within 0.08. The reference at fp8
in the program's place moves EVERY row by 0.0165 or more (median 0.023,
0.05-0.08 at the most): the precision below the stated one fails the first.
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
from distributeddeeplearning_tpu.generate import decode_step, prefill
from distributeddeeplearning_tpu.models import glm4_moe_lite as glm
from distributeddeeplearning_tpu.serving import (
    Request, ServingEngine, check_serving_composition,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    # by file, as the harness does: references/ is not a package
    path = os.path.join(REPO, "benchmarks", "references", "glm4_moe_lite.py")
    spec = importlib.util.spec_from_file_location("_ref_glm4_moe_lite", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

TINY = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, num_nextn_predict_layers=0,
    vocab_size=256, routed_scaling_factor=1.8, rope_theta=1e6,
    rms_norm_eps=1e-5, max_position_embeddings=256, torch_dtype="float32",
)
SEED = 2**31 + 5
F32_TOL = 1e-5
BF16_ROW_P90, BF16_ROW_MAX = 0.008, 0.08


def _row_gaps(got, want):
    """Widest |difference| of each row of logits [T, V]."""
    return np.abs(
        np.asarray(got, np.float32) - np.asarray(want, np.float32)
    ).max(axis=-1)


def _assert_close(rows, dtype):
    if dtype == "float32":
        assert rows.max() <= F32_TOL, rows.max()
    else:
        assert np.quantile(rows, 0.9) <= BF16_ROW_P90, np.quantile(rows, 0.9)
        assert rows.max() <= BF16_ROW_MAX, rows.max()


def _dims(**over):
    return ref.dims({**TINY, **over})


def _params(d, seed=SEED):
    return jax.jit(
        lambda k: ref.program_tree(ref.weights_from_key(k, d), d)
    )(ref.seed_key(seed))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    d = _dims()
    return d, models.get_model("glm4_moe_lite", size="tiny"), _params(d)


@pytest.fixture(autouse=True)
def _full_precision_matmuls():
    # the CPU's float32 product is exact enough; this pins it
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the whole forward pass --------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunked", [False, True], ids=["logits", "chunked"])
def test_forward_matches_the_reference(tiny, dtype, chunked):
    d, _, params = tiny
    model = models.get_model(
        "glm4_moe_lite", size="tiny", dtype=dtype, chunked_head=chunked
    )
    toks = _tokens(48)
    out = model.apply({"params": params}, toks[None])
    if chunked:
        out = jnp.einsum("ble,ve->blv", out["hidden"], out["emb"])
    want = ref.logits(ref.make_weights(SEED, d), toks, d)
    _assert_close(_row_gaps(out[0], want), dtype)


def test_the_fp8_control_fails_the_bf16_tolerance(tiny):
    d, _, _ = tiny
    toks = _tokens(48)
    w = ref.make_weights(SEED, d)
    rows = _row_gaps(ref.logits(w, toks, d, "fp8"), ref.logits(w, toks, d))
    assert rows.min() > 2 * BF16_ROW_P90, rows.min()


def test_parameters_are_created_in_the_stated_dtype():
    model = models.get_model(
        "glm4_moe_lite", size="tiny", dtype="bfloat16",
        param_dtype="bfloat16", num_mtp=1,
    )
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )
    assert set(shapes) == {"params"}  # no cache outside decode mode
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        shapes["params"]
    )[0]:
        name = str(getattr(path[-1], "key", ""))
        small = name in ("scale", "router_bias") or leaf.ndim == 1
        assert leaf.dtype == (jnp.float32 if small else jnp.bfloat16), path


def test_program_tree_is_the_models_tree():
    d = _dims(num_nextn_predict_layers=1, torch_dtype="bfloat16")
    model = models.get_model(
        "glm4_moe_lite", size="tiny", num_mtp=1, param_dtype="bfloat16"
    )
    want = flax.core.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"])
    got = jax.eval_shape(
        lambda k: ref.program_tree(ref.weights_from_key(k, d), d),
        ref.seed_key(1),
    )
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(got) == shapes(want)
    assert set(ref.leaf_names_of_program_tree(got)) >= {
        "embed.embedding", "block_1.moe.experts_gate", "mtp.eh_proj.kernel"
    }


# -- (b) the latent paged cache, logits at every position ------------------


def _paged(model, num_blocks=40, bs=4, pages=12):
    paged = model.clone(decode=True, kv_pages=(num_blocks, bs, pages))
    shapes = jax.eval_shape(
        paged.init, jax.random.PRNGKey(0), np.zeros((1, 1), np.int32)
    )["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return paged, cache


def _with_rows(cache, table, lens):
    """The engine's ``_inject``: host tables and cursors in by leaf name."""
    def pick(path, leaf):
        name = getattr(path[-1], "key", None)
        if name == "page_table":
            return jnp.asarray(table, jnp.int32)
        if name == "seq_lens":
            return jnp.asarray(lens, jnp.int32)
        return leaf
    return jax.tree_util.tree_map_with_path(pick, cache)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_through_the_latent_cache(tiny, dtype):
    # Two requests: A's 11-token prompt in a 16-wide bucket (crossing two
    # 4-token block edges, right-padded), B's 5 tokens in an 8-wide one;
    # then both decode in ONE batch at different depths beside an idle
    # lane, across further block edges. Every logit row is held to the
    # reference's full forward over the same tokens.
    d, _, params = tiny
    model = models.get_model("glm4_moe_lite", size="tiny", dtype=dtype)
    paged, cache = _paged(model)
    w = ref.make_weights(SEED, d)
    seq_a, seq_b = _tokens(19, 1), _tokens(13, 2)
    want_a, want_b = (np.asarray(ref.logits(w, s, d)) for s in (seq_a, seq_b))
    pages = {"a": np.arange(1, 13), "b": np.arange(13, 25)}
    gaps = []
    for name, seq, n, bucket, want in (
        ("a", seq_a, 11, 16, want_a), ("b", seq_b, 5, 8, want_b),
    ):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq[:n]
        out, cache = prefill(
            paged, params, _with_rows(cache, pages[name][None], [0]), toks
        )
        gaps.append(_row_gaps(out[0, :n], want[:n]))
    table = np.stack([pages["a"], pages["b"], np.zeros(12, np.int64)])
    lens = np.array([11, 5, 0])
    for step in range(8):
        tok = np.array([[seq_a[11 + step]], [seq_b[5 + step]], [0]], np.int32)
        lg, cache = decode_step(
            paged, params, _with_rows(cache, table, lens + np.array(
                [step, step, 0])), tok,
        )
        gaps.append(_row_gaps(
            lg[:2], np.stack([want_a[11 + step], want_b[5 + step]])
        ))
    _assert_close(np.concatenate(gaps), dtype)
    # the idle lane wrote the null block only
    pool = [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        if path[-1].key == "pool_latent"
    ]
    assert len(pool) == 3 and all(
        not np.asarray(p[25:], np.float32).any() for p in pool
    )


@pytest.mark.parametrize("kw", [
    {}, {"prefix_cache": True}, {"speculation": "ngram:2"},
], ids=["plain", "prefix_cache", "speculation"])
def test_engine_serves_what_the_reference_ranks_first(tiny, kw):
    # Through submit/step with the engine's own scheduler, pool and
    # programs: prompts of 17 tokens (over the 16 bucket edge, into 32), of
    # 11 sharing 8 with it (a prefix hit when the trie is on: suffix-only
    # prefill reads the latent absorbed), of 5 and of 20; every served
    # token's logit within F32_TOL of the reference's best at its position.
    d, model, params = tiny
    eng = ServingEngine(model, params, ServingConfig(
        slots=3, block_size=4, hbm_budget_mb=1, max_seq_len=64,
        prompt_buckets=(8, 16, 32), **kw,
    ))
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 17).tolist()
    prompts = [base, base[:8] + rng.integers(0, 256, 3).tolist(),
               rng.integers(0, 256, 5).tolist(), base + [1, 2, 3]]
    states = [
        eng.submit(Request(prompt=p, max_new_tokens=6 + 3 * i))
        for i, p in enumerate(prompts)
    ]
    eng.run()
    w = ref.make_weights(SEED, d)
    for st in states:
        seq = st.request.prompt + st.generated
        assert len(st.generated) == st.request.max_new_tokens
        lg = np.asarray(ref.logits(w, np.asarray(seq[:-1]), d))
        lo = len(st.request.prompt) - 1
        picked = lg[np.arange(lo, len(seq) - 1), seq[lo + 1:]]
        assert (lg[lo:].max(-1) - picked).max() <= F32_TOL
    if "prefix_cache" in kw:
        assert eng.stats()["prefix_cache"]["hit_tokens"] >= 8
    if "speculation" in kw:
        assert eng.calls["verify"] >= 1


# -- (c) absorbed against expanded attention, one layer --------------------


def test_absorbed_attention_is_the_expanded_one():
    # One MlaAttention over the paged pool: all 12 tokens at cursor 0 (the
    # expanded form), against the first token alone (L == 1: absorbed) and
    # then the other 11 at cursor 1 (L > 1 at a cursor: absorbed, reading
    # what the pool holds).
    attn = glm.MlaAttention(
        num_heads=4, q_rank=24, kv_rank=16, nope_dim=12, rope_dim=4,
        v_dim=16, rope_theta=1e6, rms_eps=1e-5, decode=True,
        kv_pages=(8, 4, 4),
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 64))
    variables = flax.core.meta.unbox(attn.init(jax.random.PRNGKey(4), x))
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(5), p.shape),
        variables["params"],
    )
    cache = jax.tree.map(jnp.zeros_like, variables["cache"])
    table = np.array([[1, 2, 3, 0]])

    def run(cache, x, cursor):
        return attn.apply(
            {"params": params, "cache": _with_rows(cache, table, [cursor])},
            x, mutable=["cache"],
        )

    expanded, _ = run(cache, x, 0)
    first, after = run(cache, x[:, :1], 0)
    rest, _ = run(after["cache"], x[:, 1:], 1)
    absorbed = jnp.concatenate([first, rest], axis=1)
    assert float(jnp.max(jnp.abs(absorbed - expanded))) <= 1e-5
    assert float(jnp.max(jnp.abs(expanded))) > 1e-2


# -- (d) routing -----------------------------------------------------------


def test_routing_drops_no_token_at_any_batch_shape():
    # The same 16 tokens as one 16-token prompt, as 16 one-token prompts
    # and as one decode batch of 16 lanes: routed alike and computed alike,
    # every (token, choice) pair counted, whatever the shape.
    moe = glm.RoutedExperts(
        num_routed_experts=8, expert_dim=48, top_k=2, routed_scale=1.8,
        decode=True,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    variables = flax.core.meta.unbox(moe.init(jax.random.PRNGKey(1), x))
    params = jax.tree.map(
        lambda p: p + 0.3 * jax.random.normal(jax.random.PRNGKey(2), p.shape),
        variables["params"],
    )

    def run(x):
        y, aux = moe.apply({"params": params}, x, mutable=["cache"])
        return y, np.asarray(aux["cache"]["expert_load"])

    bulk, load_bulk = run(x)
    lanes, load_lanes = run(x.reshape(16, 1, 64))
    singles = [run(x[:, i:i + 1]) for i in range(16)]
    assert load_bulk.sum() == load_lanes.sum() == 16 * 2
    assert (load_bulk == load_lanes).all()
    assert (sum(n for _, n in singles) == load_bulk).all()
    assert float(jnp.max(jnp.abs(lanes.reshape(1, 16, 64) - bulk))) <= 1e-5
    one_by_one = jnp.concatenate([y for y, _ in singles], axis=1)
    assert float(jnp.max(jnp.abs(one_by_one - bulk))) <= 1e-5
    # no expert was evaluated densely: a token's result is its two chosen
    # experts' and the shared one's, as the reference's plain loop gives
    lw = {
        "router": params["router"], "router_bias": params["router_bias"],
        "e_gate": params["experts_gate"], "e_up": params["experts_up"],
        "e_down": params["experts_down"],
        **{"s_" + n: params["shared"][n]["kernel"]
           for n in ("gate", "up", "down")},
    }
    d = _dims()
    want = ref.moe(x[0], lw, d, ref._mm("f32"))
    assert float(jnp.max(jnp.abs(bulk[0] - want))) <= 1e-5


def test_the_bias_selects_and_does_not_weigh():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    router = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    free, w_free = glm.route(x, router, jnp.zeros(8), 2, 1.8)
    bias = jnp.zeros(8).at[5].set(10.0)  # expert 5 wins every selection
    chosen, w = glm.route(x, router, bias, 2, 1.8)
    assert (np.asarray(chosen) == 5).any(axis=1).all()
    assert not (np.asarray(free) == 5).any(axis=1).all()
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.8, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_free).sum(-1), 1.8, rtol=1e-6)
    # the weights are the UNBIASED sigmoid scores of the chosen experts
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), 1.8 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5,
    )
    # and the reference routes the same tokens the same way
    r_chosen, r_w = ref.route(
        x, {"router": router, "router_bias": bias}, _dims()
    )
    assert (np.asarray(r_chosen) == np.asarray(chosen)).all()
    np.testing.assert_allclose(np.asarray(r_w), np.asarray(w), rtol=1e-5)


def test_readout_holds_open_positions_to_the_allowance(tiny, monkeypatch):
    # sequence_readout (what the harness compares): a position whose
    # float32 routing is decided by more than ROUTING_MARGIN reports its
    # gap as it stands, any other its gap less OPEN_ALLOWANCE, never
    # under 0; the margins are the reference's own.
    d, _, _ = tiny
    toks = _tokens(64, 4)
    picks = _tokens(64, 5)  # random tokens: gaps of ~0.3-0.6 everywhere
    w = ref.make_weights(SEED, d)
    lg = np.asarray(ref.logits(w, toks, d))
    raw = lg.max(-1) - lg[np.arange(64), picks]
    _, margin = ref.last_hidden(w["key"], toks, d)
    margin = np.asarray(margin)
    monkeypatch.setattr(ref, "ROUTING_MARGIN", float(np.median(margin)))
    monkeypatch.setattr(ref, "OPEN_ALLOWANCE", 0.25)
    best, amax, picked = ref.sequence_readout(
        w, toks.tolist(), picks.tolist(), d, pad_to=64
    )
    settled = margin > np.median(margin)
    assert 20 < settled.sum() < 44
    np.testing.assert_allclose(best, lg.max(-1), atol=1e-6)
    assert (amax == lg.argmax(-1)).all()
    np.testing.assert_allclose((best - picked)[settled], raw[settled],
                               atol=1e-6)
    np.testing.assert_allclose(
        (best - picked)[~settled], np.maximum(raw[~settled] - 0.25, 0.0),
        atol=1e-6,
    )
    assert ((best - picked)[~settled] > 0).any()  # the allowance is finite
    # at another precision (the control's own pass) nothing is taken off
    _, _, p8 = ref.sequence_readout(
        w, toks.tolist(), picks.tolist(), d, precision="fp8", pad_to=64
    )
    lg8 = np.asarray(ref.logits(w, toks, d, "fp8"))
    np.testing.assert_allclose(p8, lg8[np.arange(64), picks], atol=1e-6)


# -- (e) the multi-token-prediction module ---------------------------------


def test_mtp_module_matches_the_reference():
    d = _dims(num_nextn_predict_layers=1)
    model = models.get_model("glm4_moe_lite", size="tiny", num_mtp=1)
    toks = _tokens(40)
    main, mtp = model.apply({"params": _params(d)}, toks[None], mtp=True)
    w = ref.make_weights(SEED, d)
    assert mtp.shape == (1, 39, 256)
    assert float(jnp.max(jnp.abs(mtp[0] - ref.mtp_logits(w, toks, d)))) <= F32_TOL
    assert float(jnp.max(jnp.abs(main[0] - ref.logits(w, toks, d)))) <= F32_TOL
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        models.get_model("glm4_moe_lite", size="tiny").apply(
            {"params": _params(_dims())}, toks[None], mtp=True
        )


# -- (f) what the engine reports -------------------------------------------


def test_engine_sizes_the_pool_from_the_latent_leaf_and_counts_load(tiny):
    d, model, params = tiny
    eng = ServingEngine(model, params, ServingConfig(
        slots=3, block_size=4, hbm_budget_mb=1, max_seq_len=64,
        prompt_buckets=(8, 16),
    ))
    leaves = eng._pool_leaves()
    # one leaf a layer, [blocks, block, rank + rope rounded up to 128 lanes]
    assert [leaf.shape for leaf in leaves] == [(eng.num_blocks, 4, 128)] * 3
    held = sum(leaf[0].size * leaf.dtype.itemsize for leaf in leaves)
    assert eng.block_bytes == held == 3 * 4 * 128 * 4
    stats = eng.stats()
    assert stats["kv_bytes_per_token"] == held // 4
    assert stats["latent_bytes_per_token"] == held // 4
    assert "moe_tokens_per_expert" not in stats  # nothing has run yet
    assert eng.scheduler.gauges()["latent_bytes_per_token"] == held // 4
    for n in (5, 11, 16):
        eng.submit(Request(prompt=_tokens(n, n).tolist(), max_new_tokens=4))
    eng.run()
    stats = eng.stats()
    load = np.asarray(stats["moe_tokens_per_expert"])
    # every row the experts computed: 3 lanes a decode call, a bucket's
    # width a prefill (8 + 16 + 16), two choices each, in both expert layers
    rows = eng.calls["decode"] * 3 + 8 + 16 + 16
    assert load.shape == (2, 8) and (load.sum(axis=1) == rows * 2).all()
    assert stats["moe_load_max_over_mean"] == round(
        (load.max(axis=1) * 8 / load.sum(axis=1)).max(), 4
    )
    assert stats["moe_experts_hit_share"] == round((load > 0).mean(), 4)
    gauges = eng.scheduler.gauges()
    assert gauges["moe_tokens_per_expert"] == load.tolist()


def test_gpt2_reports_no_latent_or_expert_gauges():
    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)
    )["params"]
    eng = ServingEngine(model, params, ServingConfig(
        slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
        prompt_buckets=(8,),
    ))
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
    eng.run()
    assert not {
        k for k in {**eng.stats(), **eng.scheduler.gauges()}
        if k.startswith(("moe_", "latent_"))
    }


# -- (g) what is fenced, by name -------------------------------------------


def _cfg(name, **serving):
    return Config(
        model=ModelConfig(name=name, kwargs={}),
        serving=ServingConfig(**serving),
    )


@pytest.mark.parametrize("serving,match", [
    ({"kv_quant": "int8"}, r"kv_quant='int8' x latent paged cache"),
    ({"attn_kernel": "pallas"}, r"attn_kernel='pallas' x latent paged cache"),
    ({"prefix_cache": True, "spill_blocks": 4, "spill_codec": "int8"},
     r"spill_codec='int8' x latent paged cache"),
])
def test_what_the_latent_leaf_lacks_is_refused_by_name(serving, match):
    with pytest.raises(NotImplementedError, match=match):
        check_serving_composition(_cfg("glm4_moe_lite", **serving))
    check_serving_composition(_cfg("gpt2", **serving))  # K/V pools have it


def test_the_family_is_servable_and_capacity_moe_still_is_not():
    from distributeddeeplearning_tpu.serving.engine import SERVABLE_MODELS

    assert "glm4_moe_lite" in SERVABLE_MODELS
    check_serving_composition(_cfg(
        "glm4_moe_lite", prefix_cache=True, spill_blocks=4,
        speculation="ngram:2",
    ))
    for name in ("gpt2_moe", "llama_moe"):
        with pytest.raises(NotImplementedError, match="capacity-MoE"):
            check_serving_composition(_cfg(name))


def test_contiguous_decode_and_expert_parallel_mesh_are_refused():
    model = models.get_model("glm4_moe_lite", size="tiny", decode=True)
    with pytest.raises(NotImplementedError, match="contiguous decode cache"):
        model.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    assert not hasattr(model, "num_experts")  # Trainer: no ep axis for it


# -- the server's entry ----------------------------------------------------


def test_a_server_builds_no_optimizer_state_and_keeps_the_stated_dtype():
    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = apply_overrides(
        load_config(os.path.join(REPO, "configs", "glm4_moe_lite.py")),
        ["model.kwargs.size='tiny'", "model.kwargs.max_len=64",
         "data.vocab_size=256", "data.seq_len=16", "train.seed=3"],
    )
    _mesh, model, trainer, dataset = cli.build_all(cfg)
    model, state = cli.serving_model_and_state(cfg, model, trainer, dataset)
    assert not hasattr(state, "opt_state")
    kernel = state.params["block_1"]["moe"]["experts_gate"]
    assert kernel.dtype == jnp.bfloat16 and kernel.shape == (8, 64, 48)
    assert state.params["norm"]["scale"].dtype == jnp.float32
    # .replace(params=...) is what the benchmark's weight swap needs
    assert state.replace(params=None).params is None
    # the same parameters a trainer's init gives for the seed
    full = trainer.init(cfg.train.seed, dataset.batch(0))
    same = jax.tree.map(
        lambda a, b: bool((a == b).all()), state.params, full.params
    )
    assert all(jax.tree.leaves(same))
