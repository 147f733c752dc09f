"""Pallas paged-attention decode kernel vs the gather oracle (interpret).

The kernel (``ops/paged_attention.py``) reads the serving engine's KV pool
IN PLACE via scalar-prefetched page tables; the oracle restates the
engine's reference lowering (gather pages -> mask -> fp32 softmax) on the
kernel's [B, H, D] signature. Off-TPU the kernel runs through the
interpret-mode evaluator, so every case here exercises the exact code the
engine ships when ``serving.attn_kernel='pallas'``. Engine-level parity
(pallas engine token-for-token vs generate()) lives in tests/
test_serving.py; the real-chip compile smoke is tier 4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import (
    paged_attention,
    paged_attention_reference,
)
from distributeddeeplearning_tpu.ops.paged_attention import group_pages

pytestmark = pytest.mark.interpret


def _pool_case(key, *, B, kv_heads, num_rep, D, num_blocks, block_size,
               pages, lens, dtype=jnp.float32):
    """Random pool + per-row page tables with the engine's invariants:
    block 0 is the null block, live rows own disjoint blocks, idle rows
    (cursor 0) park their whole table on the null block."""
    kq, kk, kv, kt = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, kv_heads * num_rep, D), jnp.float32)
    pool_k = jax.random.normal(
        kk, (num_blocks, block_size, kv_heads, D), jnp.float32
    )
    pool_v = jax.random.normal(
        kv, (num_blocks, block_size, kv_heads, D), jnp.float32
    )
    # Disjoint physical blocks per live row, shuffled so logical->physical
    # is genuinely scattered (the property the kernel's index_map carries).
    perm = np.asarray(
        jax.random.permutation(kt, np.arange(1, num_blocks))
    )
    table = np.zeros((B, pages), np.int32)
    used = 0
    for b, ln in enumerate(lens):
        if ln == 0:
            continue  # idle row: whole table on the null block
        need = ln // block_size + 1
        table[b, :need] = perm[used:used + need]
        used += need
    assert used <= perm.size, "test case over-allocated the pool"
    # As the engine stores it: heads folded into the minor dimension.
    folded = (num_blocks, block_size, kv_heads * D)
    return (
        q.astype(dtype),
        pool_k.astype(dtype).reshape(folded),
        pool_v.astype(dtype).reshape(folded),
        jnp.asarray(table),
        jnp.asarray(np.asarray(lens, np.int32)),
    )


def _check(args, num_rep, atol=2e-5):
    out = paged_attention(*args, num_rep=num_rep)
    ref = paged_attention_reference(*args, num_rep=num_rep)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=atol, rtol=atol,
    )


def test_mixed_depths_match_reference():
    # Cursors land on a page boundary, mid-page, first page, and deep —
    # the pl.when page-skip and the iota column mask both get hit.
    args = _pool_case(
        jax.random.PRNGKey(0), B=4, kv_heads=3, num_rep=1, D=16,
        num_blocks=32, block_size=8, pages=6, lens=[0, 7, 8, 37],
    )
    _check(args, num_rep=1)


def test_gqa_num_rep_groups_share_kv():
    # 2 kv groups x 4 query heads each: the kernel must read ONE kv block
    # per group while attending all num_rep query heads against it.
    args = _pool_case(
        jax.random.PRNGKey(1), B=3, kv_heads=2, num_rep=4, D=32,
        num_blocks=16, block_size=8, pages=4, lens=[5, 16, 23],
    )
    _check(args, num_rep=4)


def test_idle_rows_on_null_block_are_finite():
    # An all-idle batch (the engine between requests): every row reads
    # exactly position 0 of the null block — defined, finite output that
    # matches the reference (the engine discards it either way).
    args = _pool_case(
        jax.random.PRNGKey(2), B=4, kv_heads=2, num_rep=2, D=16,
        num_blocks=8, block_size=8, pages=3, lens=[0, 0, 0, 0],
    )
    out = paged_attention(*args, num_rep=2)
    assert bool(jnp.isfinite(out).all())
    _check(args, num_rep=2)


def test_single_page_single_head_minimal():
    args = _pool_case(
        jax.random.PRNGKey(3), B=1, kv_heads=1, num_rep=1, D=8,
        num_blocks=4, block_size=8, pages=1, lens=[3],
    )
    _check(args, num_rep=1)


def test_bf16_pool_accumulates_in_fp32():
    args = _pool_case(
        jax.random.PRNGKey(4), B=2, kv_heads=2, num_rep=2, D=16,
        num_blocks=16, block_size=8, pages=4, lens=[9, 26],
        dtype=jnp.bfloat16,
    )
    _check(args, num_rep=2, atol=2e-2)


def test_scattered_table_vs_contiguous_same_logical_sequence():
    # The same logical KV written under two different physical layouts
    # must attend identically — the page table is the only indirection.
    key = jax.random.PRNGKey(5)
    B, kv_heads, D, bs, pages = 1, 2, 16, 8, 3
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, kv_heads, D))
    logical_k = jax.random.normal(kk, (pages * bs, kv_heads, D))
    logical_v = jax.random.normal(kv, (pages * bs, kv_heads, D))
    lens = jnp.asarray([19], jnp.int32)

    def build(block_ids):
        pool_k = jnp.zeros((8, bs, kv_heads * D))
        pool_v = jnp.zeros((8, bs, kv_heads * D))
        for j, blk in enumerate(block_ids):
            rows = slice(j * bs, (j + 1) * bs)
            pool_k = pool_k.at[blk].set(logical_k[rows].reshape(bs, -1))
            pool_v = pool_v.at[blk].set(logical_v[rows].reshape(bs, -1))
        table = jnp.asarray([block_ids], jnp.int32)
        return paged_attention(q, pool_k, pool_v, table, lens)

    np.testing.assert_allclose(
        build([1, 2, 3]), build([6, 2, 4]), atol=1e-6, rtol=1e-6
    )


def test_shape_validation_fails_loudly():
    args = _pool_case(
        jax.random.PRNGKey(6), B=2, kv_heads=2, num_rep=1, D=16,
        num_blocks=8, block_size=8, pages=2, lens=[1, 9],
    )
    q, pk, pv, table, lens = args
    with pytest.raises(ValueError, match="num_rep"):
        paged_attention(q, pk, pv, table, lens, num_rep=2)
    with pytest.raises(ValueError, match="page_table"):
        paged_attention(q, pk, pv, table[:1], lens)
    with pytest.raises(ValueError, match="pool_k/pool_v"):
        paged_attention(q, pk, pv[:, :4], table, lens)


# ---------------------------------------------------------------------------
# Quantized pools (serving.kv_quant='int8'): dequant fused into the DMA
# ---------------------------------------------------------------------------


def _quantize_pool(pool, d):
    """Per-(slot, head) D-vector absmax int8 quantization — the same
    layout transformer.paged_decode_attention writes: one f32 scale per
    written (token, head) vector, so scales are [num_blocks, bs, H]
    beside the [num_blocks, bs, H*D] int8 pool."""
    from distributeddeeplearning_tpu.comms_quant import block_quantize

    nb, bs, width = pool.shape
    q, s = block_quantize(jnp.asarray(pool, jnp.float32).reshape(-1), d)
    return q.reshape(nb, bs, width), s.reshape(nb, bs, width // d)


def _quant_case(key, **kw):
    q, pk, pv, table, lens = _pool_case(key, **kw)
    qk, sk = _quantize_pool(pk, kw["D"])
    qv, sv = _quantize_pool(pv, kw["D"])
    return q, qk, qv, table, lens, sk, sv


def test_quantized_kernel_matches_quantized_reference():
    # Same dequantized bytes through both lowerings: the fused in-kernel
    # dequant must agree with the gather oracle at fp tolerance.
    q, qk, qv, table, lens, sk, sv = _quant_case(
        jax.random.PRNGKey(7), B=4, kv_heads=3, num_rep=1, D=16,
        num_blocks=32, block_size=8, pages=6, lens=[0, 7, 8, 37],
    )
    out = paged_attention(q, qk, qv, table, lens, scale_k=sk, scale_v=sv)
    ref = paged_attention_reference(
        q, qk, qv, table, lens, scale_k=sk, scale_v=sv
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_quantized_vs_fp_within_drift_tolerance():
    # int8 rounding against the full-precision pool: per-vector absmax
    # keeps unit-normal attention outputs well inside the 0.05 drift bar
    # the engine probe pins (ISSUE acceptance).
    key = jax.random.PRNGKey(8)
    args = _pool_case(
        key, B=3, kv_heads=2, num_rep=2, D=32,
        num_blocks=16, block_size=8, pages=4, lens=[5, 16, 23],
    )
    q, pk, pv, table, lens = args
    fp = paged_attention(q, pk, pv, table, lens, num_rep=2)
    qk, sk = _quantize_pool(pk, 32)
    qv, sv = _quantize_pool(pv, 32)
    q8 = paged_attention(q, qk, qv, table, lens, num_rep=2,
                         scale_k=sk, scale_v=sv)
    assert float(jnp.max(jnp.abs(q8 - fp))) < 0.05


def test_quantized_gqa_mixed_depths_and_idle_rows():
    # GQA group sharing, cursors at boundary/mid-page/deep, and an idle
    # row parked on the null block — all under the int8 layout. The null
    # block's scales are ZERO (never written): the dequantized row is
    # exactly 0, matching the fp pool's zero null block, and the idle
    # row's output stays finite.
    q, qk, qv, table, lens, sk, sv = _quant_case(
        jax.random.PRNGKey(9), B=4, kv_heads=2, num_rep=4, D=16,
        num_blocks=32, block_size=8, pages=6, lens=[0, 7, 24, 37],
    )
    zero = jnp.zeros_like(sk[0])
    sk = sk.at[0].set(zero)
    sv = sv.at[0].set(zero)
    out = paged_attention(q, qk, qv, table, lens, num_rep=4,
                          scale_k=sk, scale_v=sv)
    assert bool(jnp.isfinite(out).all())
    ref = paged_attention_reference(
        q, qk, qv, table, lens, num_rep=4, scale_k=sk, scale_v=sv
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_scale_buffer_validation_fails_loudly():
    q, qk, qv, table, lens, sk, sv = _quant_case(
        jax.random.PRNGKey(10), B=2, kv_heads=2, num_rep=1, D=16,
        num_blocks=8, block_size=8, pages=2, lens=[1, 9],
    )
    fp_k = qk.astype(jnp.float32)
    # int8 pool without scales: silent garbage without the fence.
    with pytest.raises(ValueError, match="scale"):
        paged_attention(q, qk, qv, table, lens)
    # scales beside a non-int8 pool: caller confusion, not a layout.
    with pytest.raises(ValueError, match="int8"):
        paged_attention(q, fp_k, fp_k, table, lens,
                        scale_k=sk, scale_v=sv)
    # wrong scale shape (per-page instead of per-slot): fail by shape.
    with pytest.raises(ValueError, match="scale_k"):
        paged_attention(q, qk, qv, table, lens,
                        scale_k=sk[:, 0], scale_v=sv[:, 0])
    # the reference oracle enforces the same contract
    with pytest.raises(ValueError, match="scale"):
        paged_attention_reference(q, qk, qv, table, lens)


# ---------------------------------------------------------------------------
# Groups of pages: ragged cursors against the loop's every edge
# ---------------------------------------------------------------------------

# 16-token pages, 16 a group (256 tokens), a table of 40: two whole groups
# and a half. In lane order, so that a long lane's pages are what a short
# lane finds left in its slots: the full table; idle (cursor 0, null
# block); a page's last slot; 21 live pages (not a multiple of 16); a
# page's first slot; one page past a whole group; a whole group; idle.
_RAGGED = [639, 0, 15, 330, 16, 256, 255, 0]
_RAGGED_CASE = dict(B=len(_RAGGED), kv_heads=2, D=16, num_blocks=120,
                    block_size=16, pages=40, lens=_RAGGED)


def test_group_rule():
    assert group_pages(16, 64) == 16     # the served cells
    assert group_pages(8, 128) == 32
    assert group_pages(16, 3) == 3       # never past the table
    assert group_pages(512, 4) == 1      # never under a page
    assert group_pages(16, 40) == 16 and 40 % 16  # _RAGGED ends mid-group


@pytest.mark.parametrize("num_rep", [1, 4])
@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_ragged_cursors_match_reference(pool, num_rep):
    kw = dict(_RAGGED_CASE, num_rep=num_rep)
    key = jax.random.PRNGKey(11)
    if pool == "int8":
        q, pk, pv, table, lens, sk, sv = _quant_case(key, **kw)
        scales, atol = dict(scale_k=sk, scale_v=sv), 2e-5
    else:
        dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
        q, pk, pv, table, lens = _pool_case(key, dtype=dtype, **kw)
        # bf16: the kernel's own sums are f32 (K, V and q multiply exactly
        # and p keeps 16 bits); what is left is the output's rounding.
        scales, atol = {}, 1e-2 if pool == "bf16" else 2e-5
    out = paged_attention(
        q, pk, pv, table, lens, num_rep=num_rep, **scales)
    assert out.dtype == q.dtype and bool(jnp.isfinite(out).all())
    ref = paged_attention_reference(
        q.astype(jnp.float32),
        pk if pool == "int8" else pk.astype(jnp.float32),
        pv if pool == "int8" else pv.astype(jnp.float32),
        table, lens, num_rep=num_rep, **scales)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=atol, rtol=atol)


def test_pages_past_the_cursor_are_never_read():
    # A lane reads its live pages and no other: poison (NaN) in every
    # block that no lane's live pages name must not reach any output,
    # though the table's dead entries point at it.
    q, pk, pv, table, lens = _pool_case(
        jax.random.PRNGKey(12), num_rep=1, **_RAGGED_CASE)
    clean = paged_attention(q, pk, pv, table, lens)
    live = np.zeros(120, bool)
    for row, ln in zip(np.asarray(table), _RAGGED):
        live[row[:ln // 16 + 1]] = True
    dead = jnp.asarray(np.flatnonzero(~live))
    table = jnp.where(
        jnp.arange(40)[None, :] > (lens // 16)[:, None], dead[0], table)
    out = paged_attention(
        q, pk.at[dead].set(jnp.nan), pv.at[dead].set(jnp.nan), table, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
