"""Paged-attention decode kernel — Pallas Mosaic, for the serving engine.

The serving hot loop (``serving/engine.py``) decodes ONE token per row
against a block-pooled KV cache. The reference lowering
(``transformer.paged_decode_attention``) gathers each row's pages into a
contiguous ``[B, pages*block_size]`` view per layer per step — correct,
but it materializes the whole gathered cache in HBM every decode step.
This kernel reads the pool IN PLACE: the page table rides in as a
scalar-prefetch operand, so each grid step's BlockSpec index_map resolves
``page_table[b, j]`` and the DMA engine fetches exactly that physical
block — no gathered copy exists at any point.

Layout (see pallas_guide.md and ops/flash_attention.py, the idiom seed):
- grid is ``(batch, pages_per_seq)`` — pages innermost, which is
  sequential on TPU, so the online-softmax carries (m, l, acc) live in
  VMEM scratch across a row's pages;
- ``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)``: the page
  table and the per-row cursors are scalar operands available to BOTH the
  index_maps (physical block selection) and the kernel body (causal
  masking at the row's cursor);
- one grid step takes a WHOLE page as it is stored: the block
  ``(1, block_size, kv_heads*D)`` over the ``[NB, BS, G*D]`` pool has its
  last two dims equal to the array's, and they fill the (8, 128) tile with
  no padding (G*D is 768, 1024 or 512 here; 16 rows are one bf16 tile), so
  the DMA is one contiguous lane-dense run — 24 KB at GPT-2 124M. The
  pool keeps heads folded into lanes because a ``(G, D) = (12, 64)`` minor
  pair pads 2.7x and makes the runtime store the block index minor-most
  (``transformer.paged_decode_attention`` says what that cost);
- heads are split on the page in VMEM by static lane slices (Mosaic
  refuses the ``(BS, G*D) -> (BS, G, D)`` shape cast at D=64): one
  lane-dense multiply ``k * q`` per rep, then per head a lane reduce of
  its D-wide slice. The softmax state is then head-vectorised, scores
  ``(BS, G)`` and carries ``(1, G)``. No MXU: a one-token decode is an
  M=1 matmul per head, DMA-bound either way;
- GQA: q arrives group-major (query head ``g*num_rep + r`` reads kv
  group ``g``, matching ``transformer._cache_attend``) and is handed to
  the kernel as ``[B, num_rep, kv_heads*D]`` — rep ``r``'s row lines up
  with the page's lanes, so the pool is never repeated to the query head
  count;
- pages entirely beyond a row's cursor are skipped with ``pl.when`` (no
  VPU work on the accumulate path); the cursor page is masked per slot
  with ``broadcasted_iota``;
- all accumulation is fp32 regardless of pool dtype; on CPU backends the
  kernel runs in interpret mode, which is how the parity tests exercise
  it without a TPU (the TPU lowering is compiled devicelessly in
  ``tests/test_tpu_compile.py`` and run by ``chip_smoke.py``).

Semantics match the reference gather exactly: the caller has already
scattered this step's k/v into the pool at position ``seq_lens[b]``, and
row b attends columns ``0 .. seq_lens[b]`` inclusive. Idle rows (cursor
0, page table parked on the null block) attend exactly position 0 of the
null block — same as the reference; the engine discards their output.

Quantized pools (``serving.kv_quant='int8'``): the pool arrives as int8
with one f32 scale per (page slot, kv head) D-vector in parallel scale
pools ``[num_blocks, block_size, kv_heads]`` (written at scatter time by
``transformer.paged_decode_attention``). The quantized kernel variant
adds two ``(1, block_size, kv_heads)`` BlockSpec operands whose index_maps
follow the SAME ``page_table[b, j]`` indirection — the per-page DMA pulls
the int8 page AND its scale rows into VMEM together, and the dequant
(``values.astype(f32) * scale``, the ``comms_quant`` codec inverse) is
fused inline: a scale is constant over its head's D lanes, so it
multiplies the (slot, head) score and probability instead of the page
(``sum_d(k*s*q) = s*sum_d(k*q)``). The fp32 carries (m, l, acc) are
unchanged, so the only numerics delta vs the fp kernel is the
quantization grid itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # finite: exp(_NEG_INF - m) == 0 exactly, no inf-inf NaNs


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _decode_kernel(
    table_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
    sm_scale, block_size, num_pages, kv_heads, quantized,
):
    """One (row, page) grid step. ``q_ref`` (1, R, G*D); ``k_ref`` /
    ``v_ref`` (1, BS, G*D), the page as stored; quantized pools add
    ``sk_ref`` / ``sv_ref`` (1, BS, G) — the page's scale rows, fetched by
    the same ``tbl[b, j]`` index_map as the page. Carries per rep: m, l
    (1, G), acc (1, G*D)."""
    if quantized:
        sk_ref, sv_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_rep = q_ref.shape[1]
    head_dim = q_ref.shape[2] // kv_heads

    def head(x, g):  # head g's D lanes of a [.., G*D] value
        return x[:, g * head_dim:(g + 1) * head_dim]

    def per_head(fn):  # [.., G*n] from fn(g) -> [.., n]
        return jnp.concatenate([fn(g) for g in range(kv_heads)], axis=1)

    def over_lanes(x):  # (1, G) -> (1, G*D): head g's value on its lanes
        return per_head(
            lambda g: jnp.broadcast_to(x[:, g:g + 1], (1, head_dim))
        )

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = lens_ref[b]  # this row's query position (cursor, pre-advance)

    # Pages strictly beyond the cursor hold no visible columns — skip.
    @pl.when(j * block_size <= pos)
    def _page():
        k = k_ref[0].astype(jnp.float32)  # (BS, G*D)
        v = v_ref[0].astype(jnp.float32)
        col = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, 1), 0
        )
        for r in range(num_rep):
            q = q_ref[0, r:r + 1].astype(jnp.float32) * sm_scale  # (1, G*D)
            kq = k * q
            s = per_head(  # (BS, G)
                lambda g: jnp.sum(head(kq, g), axis=-1, keepdims=True)
            )
            if quantized:
                s = s * sk_ref[0]
            s = jnp.where(col <= pos, s, _NEG_INF)
            m_prev = m_scr[r]  # (1, G)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[r] = l_scr[r] * alpha + jnp.sum(p, axis=0, keepdims=True)
            m_scr[r] = m_new
            pw = p * sv_ref[0] if quantized else p
            pv = per_head(  # (1, G*D)
                lambda g: jnp.sum(
                    pw[:, g:g + 1] * head(v, g), axis=0, keepdims=True
                )
            )
            acc_scr[r] = acc_scr[r] * over_lanes(alpha) + pv

    @pl.when(j == num_pages - 1)
    def _finalize():
        for r in range(num_rep):
            l = over_lanes(jnp.maximum(l_scr[r], 1e-30))
            o_ref[0, r:r + 1] = (acc_scr[r] / l).astype(o_ref.dtype)


def _check_scales(pool_k, scale_k, scale_v, kv_heads):
    """Validate the quantized-pool operand set: int8 pools require BOTH
    scale pools with the pool's (num_blocks, block_size, kv_heads)
    layout; fp pools must not carry scales (a silently ignored scale
    buffer is a caller bug). Returns True when the pool is quantized."""
    num_blocks, block_size, _ = pool_k.shape
    quantized = pool_k.dtype == jnp.int8
    if not quantized:
        if scale_k is not None or scale_v is not None:
            raise ValueError(
                f"scale_k/scale_v passed with a non-int8 pool "
                f"(dtype {pool_k.dtype}) — scales only pair with "
                "kv_quant='int8' pools"
            )
        return False
    want = (num_blocks, block_size, kv_heads)
    for name, s in (("scale_k", scale_k), ("scale_v", scale_v)):
        if s is None:
            raise ValueError(
                f"int8 pool without {name}: quantized pools need one f32 "
                f"scale per (page slot, kv head) — shape {want}"
            )
        if tuple(s.shape) != want:
            raise ValueError(
                f"{name} shape {tuple(s.shape)} must be "
                f"[num_blocks, block_size, kv_heads] = {want}"
            )
    return True


def paged_attention(
    q, pool_k, pool_v, page_table, seq_lens, *,
    scale_k=None, scale_v=None,
    num_rep: int = 1,
    sm_scale: float | None = None,
    interpret: bool | None = None,
):
    """One decode step of attention against the paged KV pool, in place.

    - ``q``: [B, H, D] — ONE query token per row, heads group-major over
      kv groups (H = kv_heads * num_rep);
    - ``pool_k`` / ``pool_v``: [num_blocks, block_size, kv_heads*D] —
      the shared block pool as the engine stores it, heads folded into
      lanes (un-repeated kv under GQA: kv_heads = H // num_rep);
    - ``page_table``: [B, pages_per_seq] int32 — row b's logical page j
      lives in physical pool block ``page_table[b, j]``. Every entry must
      be a valid block id; out-of-range ids read whatever block the DMA
      clamps to (the caller fails loudly first — see
      ``transformer.paged_decode_attention``);
    - ``seq_lens``: [B] int32 — the row's cursor BEFORE this token
      advances it: row b attends columns ``0 .. seq_lens[b]`` of its
      logical sequence (its own just-written k/v included);
    - ``scale_k`` / ``scale_v``: [num_blocks, block_size, kv_heads] f32,
      REQUIRED iff the pool is int8 (``serving.kv_quant='int8'``) — the
      per-(slot, head) dequant scales, DMA'd per page beside the int8
      block and applied inline before the dots.

    Returns [B, H, D] in q's dtype. ``interpret=None`` auto-selects
    interpret mode off-TPU (the CPU test harness).
    """
    B, H, D = q.shape
    if pool_k.ndim != 3 or pool_v.shape != pool_k.shape:
        raise ValueError(
            f"pool_k/pool_v must share one [NB,bs,kv_heads*D] shape: "
            f"{pool_k.shape} {pool_v.shape}"
        )
    num_blocks, block_size, width = pool_k.shape
    kv_heads = H // num_rep
    if H != kv_heads * num_rep or width != kv_heads * D:
        raise ValueError(
            f"q [B,H,D]={q.shape} incompatible with pool "
            f"[NB,bs,kv_heads*D]={pool_k.shape} at num_rep={num_rep}"
        )
    num_pages = page_table.shape[-1]
    if page_table.shape != (B, num_pages) or seq_lens.shape != (B,):
        raise ValueError(
            f"page_table {page_table.shape} / seq_lens {seq_lens.shape} "
            f"must be [B={B}, pages] / [B={B}]"
        )
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(D))
    if interpret is None:
        interpret = _default_interpret()
    quantized = _check_scales(pool_k, scale_k, scale_v, kv_heads)

    # Group-major head fold: head g*num_rep+r -> (group g, rep r), then
    # rep-major so each rep's G*D row matches the page's lanes.
    q3 = q.reshape(B, kv_heads, num_rep, D).transpose(0, 2, 1, 3).reshape(
        B, num_rep, width
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_size=block_size,
        num_pages=num_pages, kv_heads=kv_heads, quantized=quantized,
    )
    q_spec = pl.BlockSpec(
        (1, num_rep, width), lambda b, j, tbl, lens: (b, 0, 0)
    )
    # The paged reads: physical block (and, quantized, its scale rows)
    # straight off the scalar-prefetched table.
    page_spec = pl.BlockSpec(
        (1, block_size, width), lambda b, j, tbl, lens: (tbl[b, j], 0, 0),
    )
    in_specs = [q_spec, page_spec, page_spec]
    operands = [q3, pool_k, pool_v]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, block_size, kv_heads),
            lambda b, j, tbl, lens: (tbl[b, j], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [scale_k, scale_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((num_rep, 1, kv_heads), jnp.float32),
            pltpu.VMEM((num_rep, 1, kv_heads), jnp.float32),
            pltpu.VMEM((num_rep, 1, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, num_rep, width), q.dtype),
        name="paged_decode",
        interpret=interpret,
    )(
        jnp.asarray(page_table, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        *operands,
    )
    return out.reshape(B, num_rep, kv_heads, D).transpose(
        0, 2, 1, 3
    ).reshape(B, H, D)


def paged_attention_reference(q, pool_k, pool_v, page_table, seq_lens, *,
                              scale_k=None, scale_v=None, num_rep: int = 1):
    """Pure-jnp oracle: the engine's gather lowering, kernel-level shapes.

    Same math as ``transformer.paged_decode_attention``'s reference path
    (gather pages -> mask ``col <= cursor`` -> fp32 softmax), restated on
    the kernel's [B, H, D] single-token signature for parity tests. With
    an int8 pool the gathered pages dequantize against the gathered scale
    rows — the same dequant-on-gather lowering the engine ships.
    """
    B, H, D = q.shape
    nb, bs, _ = pool_k.shape
    kv_heads = H // num_rep
    pages = page_table.shape[-1]
    quantized = _check_scales(pool_k, scale_k, scale_v, kv_heads)
    pool_k = pool_k.astype(jnp.float32).reshape(nb, bs, kv_heads, D)
    pool_v = pool_v.astype(jnp.float32).reshape(nb, bs, kv_heads, D)
    if quantized:
        pool_k = pool_k * scale_k[..., None]
        pool_v = pool_v * scale_v[..., None]
    ck = pool_k[page_table].reshape(B, pages * bs, kv_heads, D)
    cv = pool_v[page_table].reshape(B, pages * bs, kv_heads, D)
    qg = q.reshape(B, kv_heads, num_rep, D)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, ck).astype(jnp.float32)
    s = s / np.sqrt(D)
    cols = jnp.arange(pages * bs)
    s = jnp.where(
        cols[None, None, None, :] <= seq_lens[:, None, None, None],
        s, _NEG_INF,
    )
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, cv.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
