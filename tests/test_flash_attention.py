"""Pallas flash-attention kernel vs the pure-jnp oracle (interpret mode).

SURVEY.md §4 tier 1: Pallas kernels are tested on CPU in interpret mode
against materialized-softmax references; the TPU compile lives in
tests/test_tpu_compile.py and the on-chip parity in chip_smoke.py.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import (
    attention_reference,
    flash_attention,
)


def _module():
    # ``ops.flash_attention`` names the function; the module under it holds
    # the tile rule.
    return sys.modules["distributeddeeplearning_tpu.ops.flash_attention"]


def _qkv(key, b=2, s=256, h=2, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    mk = lambda k: jax.random.normal(k, shape, jnp.float32).astype(dtype)  # noqa: E731
    return mk(kq), mk(kk), mk(kv)


# (shape of q/k/v, blocks): the named blocks the suite began with, then the
# derived default tiles (no block named): one tile; several of them, so that
# the sweep inside the kernel and its causal bound run more than once; a
# length the rule pads (1100 -> 1152, three 384-row blocks); bfloat16 inputs.
_TILE_CASES = {
    "blocks128": (dict(), dict(block_q=128, block_k=128)),
    "default_one_tile": (dict(), {}),
    "default_several_tiles": (dict(b=1, s=1536, h=2, d=32), {}),
    "default_padded": (dict(b=1, s=1100, h=1, d=32), {}),
    "default_bf16": (dict(b=1, s=1024, h=2, dtype=jnp.bfloat16), {}),
}


def _tol(q, f32):
    return dict(atol=f32, rtol=f32) if q.dtype == jnp.float32 else dict(
        atol=3e-2, rtol=3e-2
    )


def _f32(tree):
    return jax.tree.map(lambda t: np.asarray(t, np.float32), tree)


@pytest.mark.parametrize("case", list(_TILE_CASES))
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal, case):
    shape, blocks = _TILE_CASES[case]
    q, k, v = _qkv(jax.random.PRNGKey(0), **shape)
    out = flash_attention(q, k, v, causal=causal, **blocks)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(_f32(out), _f32(ref), **_tol(q, 2e-5))


def test_forward_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(1), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=2e-2, rtol=2e-2
    )


def test_multi_block_unequal_blocks():
    # 4 q-blocks x 2 kv-blocks exercises the scratch-carry across the grid.
    q, k, v = _qkv(jax.random.PRNGKey(2), s=256)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


_GRAD_CASES = {
    "one_tile": dict(s=128, d=32),
    "several_tiles": dict(b=1, s=1024, h=2, d=32),
    "padded": dict(b=1, s=1100, h=1, d=32),
    "bf16": dict(b=1, s=1024, h=1, d=32, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_GRAD_CASES))
@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal, case):
    # Every case at the derived default tiles (no block named).
    q, k, v = _qkv(jax.random.PRNGKey(3), **_GRAD_CASES[case])
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=causal).astype(jnp.float32) * w
        )

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            _f32(gf), _f32(gr), **_tol(q, 5e-5), err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
def test_several_major_blocks_match_reference(causal, monkeypatch):
    # A sequence too long for K and V (dK/dV: Q and dO) to stay in VMEM whole
    # is swept in several major blocks on the grid's third axis, with the
    # swept pair's index map clamped to the causal diagonal. 32K rows do that
    # on the chip; here the cap is shrunk until 128 rows do: 4 major blocks
    # of one 32-row tile for q (block 32), 2 of one 64-row tile for k.
    fa = _module()
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 1)
    cut = fa.tiles(128, 32, jnp.float32, causal, 32, 64)
    assert (cut.major_q, cut.major_k) == (32, 64)
    q, k, v = _qkv(jax.random.PRNGKey(18), s=128, d=32)
    w = jax.random.normal(jax.random.PRNGKey(19), q.shape)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal, **kw) * w)

    blocks = dict(block_q=32, block_k=64)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=causal, **blocks),
        attention_reference(q, k, v, causal=causal), atol=2e-5, rtol=2e-5,
    )
    g_flash = jax.grad(loss(flash_attention, **blocks), argnums=(0, 1, 2))(
        q, k, v
    )
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


def test_tile_rule_grid_and_divisibility():
    # The rule itself: at the training cell's call, bf16[8*16, 1024, 64]
    # causal, at most 1,024 grid steps a kernel (the 128 x 128 grid had
    # 8,192); and for any length no block that fails to divide the padded
    # sequence, no major block that fails to hold whole tiles.
    fa = _module()
    cut = fa.tiles(1024, 64, jnp.bfloat16, True)
    bh = 8 * 16
    assert bh * (cut.seq // cut.block_q) * (cut.seq // cut.major_k) <= 1024
    assert bh * (cut.seq // cut.block_k) * (cut.seq // cut.major_q) <= 1024
    assert cut.seq == 1024
    for seq in (8, 17, 64, 100, 128, 197, 509, 1000, 1024, 1100, 4096, 5000,
                32768):
        for causal in (False, True):
            for dtype, d in ((jnp.bfloat16, 64), (jnp.float32, 128)):
                cut = fa.tiles(seq, d, dtype, causal)
                assert seq <= cut.seq < seq + 128, (seq, cut)
                for blk, maj in ((cut.block_q, cut.major_q),
                                 (cut.block_k, cut.major_k)):
                    assert cut.seq % maj == 0 and maj % blk == 0, (seq, cut)
    # Named blocks: cut to the sequence, one common block where they do not
    # divide it (the pad stays under a block).
    assert fa.tiles(256, 64, jnp.float32, True, 64, 128)[:3] == (64, 128, 256)
    assert fa.tiles(96, 64, jnp.float32, True, 64, 64)[:3] == (64, 64, 128)
    assert fa.tiles(64, 64, jnp.float32, True, 128, 128)[:3] == (64, 64, 64)


def test_grads_under_jit_and_blocks():
    q, k, v = _qkv(jax.random.PRNGKey(5), s=128, d=32)

    @jax.jit
    def g(q, k, v):
        f = lambda *a: jnp.sum(  # noqa: E731
            flash_attention(*a, causal=True, block_q=32, block_k=64) ** 2
        )
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    r = lambda *a: jnp.sum(  # noqa: E731
        attention_reference(*a, causal=True) ** 2
    )
    g_ref = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g(q, k, v), g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5)


def test_non_block_multiple_seq_is_padded():
    # Sequences that don't divide the block grid (ViT's 197 tokens) are
    # right-padded with masked kv columns, not rejected.
    for s, blocks in ((96, dict(block_q=64, block_k=64)), (197, {})):
        q, k, v = _qkv(jax.random.PRNGKey(6), s=s)
        for causal in (False, True):
            ref = attention_reference(q, k, v, causal=causal)
            out = flash_attention(q, k, v, causal=causal, **blocks)
            np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)
    # Gradients through the pad/slice wrapper.
    q, k, v = _qkv(jax.random.PRNGKey(9), s=197)
    f = lambda *a: jnp.sum(flash_attention(*a, causal=False) ** 2)  # noqa: E731
    r = lambda *a: jnp.sum(attention_reference(*a, causal=False) ** 2)  # noqa: E731
    for gf, gr in zip(
        jax.grad(f, argnums=(0, 1, 2))(q, k, v),
        jax.grad(r, argnums=(0, 1, 2))(q, k, v),
    ):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5)


def test_flash_under_mesh_runs_in_shard_map():
    # With an ambient activation mesh the kernel runs inside shard_map over
    # (dp,fsdp)×tp instead of being replicated around by the partitioner
    # (ADVICE r1 #1); outputs must stay sharded and exact.
    from distributeddeeplearning_tpu.sharding import activation_mesh

    from helpers import mesh_of

    mesh = mesh_of(dp=2, tp=2)
    q, k, v = _qkv(jax.random.PRNGKey(10), b=4, s=64, h=4)
    ref = attention_reference(q, k, v, causal=True)
    with activation_mesh(mesh):
        out = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
        )(q, k, v)
        grads = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True) ** 2
                ),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(grads, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_gpt2_flash_mesh_train_parity():
    # The workload wiring (configs/gpt2_owt.py: attn_impl='flash'): training
    # through the kernel on a dp×tp mesh matches the single-device xla run.
    from distributeddeeplearning_tpu.mesh import single_device_mesh

    from helpers import mesh_of, train_tiny_gpt2

    ref, _ = train_tiny_gpt2(single_device_mesh(), n_steps=4)
    flash, _ = train_tiny_gpt2(
        mesh_of(dp=2, tp=2), attn_impl="flash", n_steps=4
    )
    np.testing.assert_allclose(ref, flash, rtol=2e-4, atol=2e-5)


def test_transformer_flash_matches_xla():
    """GPT-2-shaped block: attn_impl='flash' == attn_impl='xla' fwd + grads."""
    from distributeddeeplearning_tpu.models.transformer import TransformerStack

    def make(impl):
        return TransformerStack(
            num_layers=2, num_heads=4, head_dim=16, mlp_dim=128,
            causal=True, attn_impl=impl,
        )

    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64))
    params = make("xla").init(jax.random.PRNGKey(8), x)
    out_x = make("xla").apply(params, x)
    out_f = make("flash").apply(params, x)
    np.testing.assert_allclose(out_f, out_x, atol=1e-5, rtol=1e-5)

    gx = jax.grad(lambda p: jnp.sum(make("xla").apply(p, x) ** 2))(params)
    gf = jax.grad(lambda p: jnp.sum(make("flash").apply(p, x) ** 2))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4),
        gx, gf,
    )


@pytest.mark.parametrize("seq,blocks", [
    (64, {}), (64, dict(block_q=32, block_k=32)), (2048, {}),
], ids=["default_one_tile", "blocks32", "default_several_tiles"])
def test_kv_valid_lens_match_masked_reference(seq, blocks):
    # Per-sequence key-padding limits (the contiguous-prefix mask case):
    # valid query rows must match a -inf-masked reference; padded rows are
    # garbage by contract (the loss masks them).
    def ref_attn(q, k, v, vl):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = s / np.sqrt(d)
        col = jnp.arange(s.shape[-1])
        keep = col[None, None, None, :] < vl[:, None, None, None]
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    q, k, v = _qkv(
        jax.random.PRNGKey(11), b=4, s=seq, h=4 if seq == 64 else 1, d=16
    )
    vl = jnp.array([64, 37, 50, 12], jnp.int32) * (seq // 64)
    ref = ref_attn(q, k, v, vl)
    # block 32 -> 2 kv blocks, with vl values crossing block boundaries and
    # one sequence (12 < 32) whose SECOND block is fully masked — exercises
    # the online-softmax recurrence over masked trailing blocks. At 2048 the
    # default tiles are 512 x 1024: the same over the sweep inside the kernel.
    out = flash_attention(q, k, v, kv_valid_lens=vl, **blocks)
    for i in range(4):
        n = int(vl[i])
        np.testing.assert_allclose(
            out[i, :n], ref[i, :n], atol=5e-5, rtol=5e-5
        )
    # Gradients with a validity-weighted loss (padded rows contribute 0).
    wmask = (jnp.arange(seq)[None, :] < vl[:, None]).astype(jnp.float32)
    wmask = wmask[:, :, None, None]

    def loss(fn):
        return lambda q, k, v: ((fn(q, k, v) * wmask) ** 2).sum()

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, kv_valid_lens=vl, **blocks
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: ref_attn(q, k, v, vl)), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_transformer_flash_accepts_padding_mask():
    # BERT-style: attn_impl='flash' with a [batch, k_len] contiguous-prefix
    # mask must match the xla core on valid rows.
    from distributeddeeplearning_tpu.models.transformer import TransformerStack

    def make(impl):
        return TransformerStack(
            num_layers=2, num_heads=4, head_dim=16, mlp_dim=128,
            causal=False, attn_impl=impl,
        )

    x = jax.random.normal(jax.random.PRNGKey(12), (2, 64, 64))
    vl = jnp.array([64, 40], jnp.int32)
    mask = (jnp.arange(64)[None, :] < vl[:, None]).astype(jnp.int32)
    params = make("xla").init(jax.random.PRNGKey(13), x, mask)
    out_x = make("xla").apply(params, x, mask)
    out_f = make("flash").apply(params, x, mask)
    for i in range(2):
        n = int(vl[i])
        np.testing.assert_allclose(
            out_f[i, :n], out_x[i, :n], atol=2e-5, rtol=2e-5
        )


def test_non_prefix_mask_poisons_output_to_nan():
    # Data-dependent contiguity can't raise under jit; the contract is that
    # a non-prefix mask (e.g. left padding) produces NaNs, never silently
    # wrong attention.
    from distributeddeeplearning_tpu.models.transformer import SelfAttention

    x = jax.random.normal(jax.random.PRNGKey(14), (2, 8, 64))
    good = jnp.array([[1] * 8, [1] * 5 + [0] * 3], jnp.int32)
    bad = jnp.array([[1] * 8, [0, 0, 1, 1, 1, 1, 1, 1]], jnp.int32)
    attn = SelfAttention(num_heads=4, head_dim=16, attn_impl="flash")
    params = attn.init(jax.random.PRNGKey(15), x, good)
    out_good = attn.apply(params, x, good)
    out_bad = attn.apply(params, x, bad)
    assert np.isfinite(np.asarray(out_good)).all()
    assert np.isnan(np.asarray(out_bad[1])).all()  # the left-padded row
    assert np.isfinite(np.asarray(out_bad[0])).all()  # others untouched


def test_bert_mlm_file_workload_stays_on_flash_happy_path(tmp_path, mesh8):
    # VERDICT r3 Weak #5: the shipped bert_mlm config puts flash attention on
    # the hot path while flash accepts only contiguous-prefix masks. The REAL
    # file-backed MLM pipeline (DDLTOK01 -> TokenFileMLM) emits PACKED
    # fixed-length rows with no padding mask at all (mask=None — the flash
    # happy path); this test pins that at workload shapes: file data, mlm
    # masking, flash Trainer steps, finite loss.
    import numpy as np_  # local alias; module np is jax-backed elsewhere

    from distributeddeeplearning_tpu import models
    from distributeddeeplearning_tpu.data import make_dataset, sharded_batches
    from distributeddeeplearning_tpu.data_text import write_token_file
    from distributeddeeplearning_tpu.train import (
        Trainer, get_task, make_optimizer,
    )

    path = str(tmp_path / "wiki.tok")
    rng = np_.random.default_rng(0)
    write_token_file(path, rng.integers(4, 250, 16385, dtype=np_.int64), 256)
    ds = make_dataset(
        "token_file_mlm", path=path, batch_size=16, seq_len=128,
        mask_prob=0.15, mask_token_id=3,
    )
    model = models.get_model(
        "bert", size="tiny", vocab_size=256, max_len=128, dropout_rate=0.0,
        attn_impl="flash",
    )
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("mlm"), mesh8,
        donate=False,
    )
    state = trainer.init(0, ds.batch(0))
    for i, batch in enumerate(sharded_batches(ds.iter_from(0), mesh8)):
        if i >= 2:
            break
        state, metrics = trainer.train_step(state, batch)
        assert np.isfinite(float(metrics["loss"])), metrics
    # Workload-shaped loud-failure mode: if padded inputs DID reach this
    # model with a non-prefix (e.g. left-padded) mask, the output must be
    # NaN-poisoned on that row — never silently-wrong attention.
    tokens = jnp.asarray(ds.batch(0)["input_tokens"][:2])
    bad_mask = jnp.concatenate(
        [jnp.ones((1, 128), jnp.int32),
         jnp.concatenate(
             [jnp.zeros((1, 64), jnp.int32), jnp.ones((1, 64), jnp.int32)], 1
         )],
        0,
    )
    out = model.apply({"params": state.params}, tokens, bad_mask)
    out = np.asarray(out)
    assert np.isnan(out[1]).all()
    assert np.isfinite(out[0]).all()


def test_bert_flash_with_padding_matches_xla():
    # End-to-end: BERT with attn_impl='flash' on a padded batch matches the
    # xla core on valid positions.
    from distributeddeeplearning_tpu import models

    tokens = jax.random.randint(jax.random.PRNGKey(16), (2, 32), 0, 64)
    mask = jnp.array([[1] * 32, [1] * 20 + [0] * 12], jnp.int32)
    kw = dict(size="tiny", vocab_size=64, max_len=64, dropout_rate=0.0)
    xla = models.get_model("bert", **kw)
    flash = models.get_model("bert", attn_impl="flash", **kw)
    params = xla.init(jax.random.PRNGKey(17), tokens, mask)
    out_x = xla.apply(params, tokens, mask)
    out_f = flash.apply(params, tokens, mask)
    np.testing.assert_allclose(
        out_f[0], out_x[0], atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(
        out_f[1, :20], out_x[1, :20], atol=2e-4, rtol=2e-4
    )
