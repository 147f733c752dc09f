"""Paged-attention decode kernel — Pallas Mosaic, for the serving engine.

The serving hot loop (``serving/engine.py``) decodes ONE token per row
against a block-pooled KV cache. The gather lowering
(``transformer.paged_decode_attention``) copies every page of every row's
table into a contiguous ``[B, pages*block_size]`` view per layer per step,
whatever the rows hold. This kernel reads a row's LIVE pages where they
lie: the page table and the cursors ride in as scalar-prefetch operands,
the pools stay in HBM, and the kernel copies ``table[b, j]`` for the pages
up to the row's cursor and no other. The engine takes it by its own rule
(``serving.engine.read_path``).

Layout (see pallas_guide.md and ops/flash_attention.py, the idiom seed):
- the grid is ``(batch,)``, one row (lane) a grid step, sequential; inside
  it a loop over GROUPS of G pages (``group_pages``: 256 tokens), only as
  many as the row's ``seq_lens[b] // block_size + 1`` live pages need. A
  grid of ``(batch, pages)`` with one 24 KB page a step was bound by its
  8,192 grid steps a call, two thirds of them past a cursor (PERF.md §6,
  PR 32: 2.83 ms a call at the GPT-2 cell's shapes against 0.34 here);
- a row's pages are not contiguous, so no ``BlockSpec`` can fetch a
  group: each live page is one ``pltpu.make_async_copy`` of the block
  ``[block_size, kv_heads*D]`` as it is stored (lane-dense, contiguous:
  ``transformer.paged_decode_attention`` says why the pool folds heads
  into lanes) into one of two VMEM slots. While a group is attended the
  next one is in flight in the other slot, and behind a row's last group
  the next row's first: the slot a row starts in is carried in SMEM;
- scores and weighted sum run on the MXU against the pages AS STORED,
  heads never split: the query is laid block-diagonal, row ``r*G + g``
  holding rep r's q on head g's D lanes and 0 elsewhere, so
  ``q_diag [rows, G*D] . K^T [G*D, tokens]`` is every head's scores with
  the tokens on the lanes, and ``P [rows, tokens] . V [tokens, G*D]`` is
  every head's output on its own lanes of its own row (the other lanes of
  a row, a head's weights on another head's V, are dropped at the end).
  GQA: the ``num_rep`` query heads of a group are more rows against the
  same un-repeated page (q arrives group-major, query head
  ``g*num_rep + r`` reads kv group ``g``, as ``transformer._cache_attend``);
- bf16 q against bf16 (or int8) pages multiplies exactly into f32 sums in
  one MXU pass; the probabilities go in as two bf16 terms (16 bits of
  mantissa) stacked on the rows, so V passes the MXU once. f32 pools or
  queries take the six-pass f32 product. m, l and acc are f32 loop
  carries;
- a page past the cursor inside a row's last group is not copied: its
  slot keeps an earlier group's page (pool data; zeros before any), and
  the cursor mask (``broadcasted_iota`` over the tokens) gives every
  column past the cursor weight 0;
- on CPU backends the kernel runs in interpret mode, which is how the
  parity tests exercise it without a TPU (the TPU lowering is compiled
  devicelessly in ``tests/test_tpu_compile.py`` and run by
  ``chip_smoke.py``).

Semantics match the gather exactly: the caller has already scattered this
step's k/v into the pool at position ``seq_lens[b]``, and row b attends
columns ``0 .. seq_lens[b]`` inclusive. Idle rows (cursor 0, page table
parked on the null block) attend exactly position 0 of the null block —
same as the gather; the engine discards their output.

Quantized pools (``serving.kv_quant='int8'``): the pool arrives as int8
with one f32 scale per (page slot, kv head) D-vector in parallel scale
pools ``[num_blocks, block_size, kv_heads]`` (written at scatter time by
``transformer.paged_decode_attention``). An int8 page goes to the MXU as
bf16 (exact) and the dequant (``values.astype(f32) * scale``, the
``comms_quant`` codec inverse) is applied where it is cheapest: a scale is
constant over its head's D lanes, so it multiplies the (head, token) score
and probability instead of the page (``sum_d(k*s*q) = s*sum_d(k*q)``). The
scale pools are head-minor, which no copy on the chip can slice a page out
of (a minor dimension under 128), so the rows' scales, a sixteenth of the
pages' bytes, are gathered outside the kernel and handed over as the
scores lie. The only numerics delta vs the fp kernel is the quantization
grid itself.
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


# Tokens a loop step of the kernel attends: two lane tiles of scores. On
# the chip (PERF.md §6, PR 32) 64 tokens a step took 1.7 times as long as
# 128 over full tables, and 256 a fifth less than 128; equal where rows
# are short, which pay by the row and not by the token.
_GROUP_TOKENS = 256


def group_pages(block_size: int, num_pages: int) -> int:
    """Pages the kernel fetches and attends in one loop step (G): as many
    as make :data:`_GROUP_TOKENS` tokens, whole lane tiles of scores; at
    least one page, at most the table. One rule from the shapes, as
    ``flash_attention.tiles`` is."""
    return max(1, min(int(num_pages), _GROUP_TOKENS // int(block_size)))


def _head_selector(num_rep: int, kv_heads: int, head_dim: int):
    """The kernel's block-diagonal layout as two constant masks over its
    rows ``i = r * kv_heads + g`` (padded to a multiple of 16, a bf16
    tile): ``heads[i, lane]`` is 1 where ``lane`` is one of head g's D
    lanes, ``reps[r, i, 0]`` where row i belongs to rep r."""
    rows = -(-num_rep * kv_heads // 16) * 16
    i = np.arange(rows)[:, None]
    live = i < num_rep * kv_heads
    lane_head = np.arange(kv_heads * head_dim)[None, :] // head_dim
    heads = live & (i % kv_heads == lane_head)
    reps = np.stack([live & (i // kv_heads == r) for r in range(num_rep)])
    return heads.astype(np.float32), reps.astype(np.float32)


def _decode_kernel(
    table_ref, lens_ref, q_ref, heads_ref, reps_ref, *rest,
    sm_scale, block_size, num_pages, group, quantized,
):
    """One lane a grid step. ``q_ref`` (1, R, G*D); ``heads_ref``
    (rows, G*D) and ``reps_ref`` (R, rows, 1), :func:`_head_selector`;
    quantized pools add ``sk_ref``
    / ``sv_ref`` (1, groups, rows, tokens), the lane's scales with tokens
    on the lanes; ``k_hbm`` / ``v_hbm`` the pools where they lie; ``kbuf``
    / ``vbuf`` (2, group, BS, G*D): two slots of pages as stored; ``sems``
    (2, 2): slot x (K, V); ``slot_ref``: the slot that holds this lane's
    first group, carried from lane to lane."""
    if quantized:
        sk_ref, sv_ref, *rest = rest
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, slot_ref = rest
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    num_rep, width = q_ref.shape[1], q_ref.shape[2]
    rows = heads_ref.shape[0]
    tokens = group * block_size
    f32, bf16 = jnp.float32, jnp.bfloat16
    # bf16 q against bf16 or int8 pages multiplies exactly into the MXU's
    # f32 sums; anything else (f32 pools, f32 q) takes the f32 product.
    exact = q_ref.dtype == bf16 and kbuf.dtype in (bf16, jnp.int8)
    precision = None if exact else jax.lax.Precision.HIGHEST

    def live_pages(lane):
        return jnp.minimum(lens_ref[lane] // block_size + 1, num_pages)

    def each_live_page(lane, j, slot, do):
        # Only the lane's live pages move: a slot's other pages keep what
        # an earlier group left there (pool data, or the zeros of _first),
        # and the cursor mask gives them weight 0.
        n = live_pages(lane)
        for i in range(group):
            @pl.when(j * group + i < n)
            def _():
                at = table_ref[lane, j * group + i]
                do(pltpu.make_async_copy(
                    k_hbm.at[at], kbuf.at[slot, i], sems.at[slot, 0]))
                do(pltpu.make_async_copy(
                    v_hbm.at[at], vbuf.at[slot, i], sems.at[slot, 1]))

    def start(lane, j, slot):
        each_live_page(lane, j, slot, lambda dma: dma.start())

    def wait(lane, j, slot):
        each_live_page(lane, j, slot, lambda dma: dma.wait())

    @pl.when(b == 0)
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    pos = lens_ref[b]  # this row's query position (cursor, pre-advance)
    num_groups = (live_pages(b) + group - 1) // group
    slot0 = slot_ref[0]
    # The block-diagonal query: row r*G+g holds rep r's q on head g's
    # lanes and 0 elsewhere, so one product against a page as it is stored
    # gives every head's scores, [rows, tokens], tokens on the lanes.
    q = q_ref[0].astype(f32)
    qd = heads_ref[...] * sum(
        q[r:r + 1] * reps_ref[r] for r in range(num_rep)
    )
    if exact:
        qd = qd.astype(bf16)

    def stored(buf, slot):  # (group, BS, G*D) -> (tokens, G*D)
        x = buf[slot]
        if exact and x.dtype == bf16 and block_size % 16 == 0:
            return x.reshape(tokens, width)  # whole bf16 tiles: no cast
        x = x.astype(f32).reshape(tokens, width)
        return x.astype(bf16) if exact else x

    def body(j, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + j) % 2
        # Fetch ahead into the other slot: this lane's next group, or,
        # behind its last, the next lane's first.
        @pl.when(j + 1 < num_groups)
        def _():
            start(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(j + 1 == num_groups, b + 1 < lanes))
        def _():
            start(b + 1, 0, 1 - slot)

        wait(b, j, slot)
        s = jax.lax.dot_general(
            qd, stored(kbuf, slot), (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=f32,
        ) * sm_scale  # (rows, tokens)
        if quantized:
            # A scale is constant over its head's D lanes, so it
            # multiplies the score and the probability, not the page.
            s = s * sk_ref[0, j]
        col = j * tokens + jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
        s = jnp.where(col <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * sv_ref[0, j]
        v = stored(vbuf, slot)
        if exact:
            # p as two bf16 terms in one product: v passes the MXU once
            # and p keeps 16 bits of its f32 mantissa.
            hi = p.astype(bf16)
            lo = (p - hi.astype(f32)).astype(bf16)
            pv = jnp.dot(
                jnp.concatenate([hi, lo], axis=0), v,
                preferred_element_type=f32,
            )
            pv = pv[:rows] + pv[rows:]
        else:
            pv = jnp.dot(
                p, v, precision=precision, preferred_element_type=f32
            )
        return m_new, l_new, acc * alpha + pv  # acc (rows, G*D)

    _, l, acc = jax.lax.fori_loop(0, num_groups, body, (
        jnp.full((rows, 1), _NEG_INF, f32), jnp.zeros((rows, 1), f32),
        jnp.zeros((rows, width), f32),
    ))
    slot_ref[0] = (slot0 + num_groups) % 2
    # Row r*G+g's own lanes are head g's output; the rest of the row, a
    # head's weights on another head's V, goes.
    out = acc / jnp.maximum(l, 1e-30) * heads_ref[...]
    for r in range(num_rep):
        o_ref[0, r:r + 1] = jnp.sum(
            out * reps_ref[r], axis=0, keepdims=True
        ).astype(o_ref.dtype)


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
    _check_scales(pool_k, scale_k, scale_v, kv_heads)
    if not interpret and width % 128:
        raise NotImplementedError(
            f"paged_attention x pool width {width} (kv_heads*head_dim): "
            "the chip copies a page out of the pool only in whole 128-lane "
            "rows — the gather read path serves such a (toy) width"
        )
    return _paged_call(
        q, pool_k, pool_v, jnp.asarray(page_table, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32), scale_k, scale_v,
        int(num_rep), float(sm_scale), bool(interpret),
    )


# jit here: a model calls paged_attention once a layer with the same shapes,
# and the jit's cache hands every call after the first the same jaxpr, so
# the kernel is traced, and lowered to Mosaic, once a program and not once
# a layer (flash_attention._fwd says what that cost a start).
@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _paged_call(q, pool_k, pool_v, page_table, seq_lens, scale_k, scale_v,
                num_rep, sm_scale, interpret):
    B, H, D = q.shape
    _, block_size, width = pool_k.shape
    kv_heads = H // num_rep
    num_pages = page_table.shape[-1]
    quantized = scale_k is not None

    # Group-major head fold: head g*num_rep+r -> (group g, rep r), then
    # rep-major so each rep's G*D row matches the page's lanes.
    q3 = q.reshape(B, kv_heads, num_rep, D).transpose(0, 2, 1, 3).reshape(
        B, num_rep, width
    )
    group = group_pages(block_size, num_pages)
    groups = -(-num_pages // group)
    heads, reps = _head_selector(num_rep, kv_heads, D)
    rows = heads.shape[0]
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_size=block_size,
        num_pages=num_pages, group=group, quantized=quantized,
    )
    q_spec = pl.BlockSpec((1, num_rep, width), lambda b, tbl, lens: (b, 0, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec(heads.shape, lambda b, tbl, lens: (0, 0)),
        pl.BlockSpec(reps.shape, lambda b, tbl, lens: (0, 0, 0)),
    ]
    operands = [q3, jnp.asarray(heads), jnp.asarray(reps)]
    if quantized:
        # The scales, a sixteenth of the pages' bytes and stored
        # kv_heads-minor (no slice of that pool is a copy the chip can
        # make), are gathered here and handed over a lane at a time as the
        # kernel's scores lie: [B, groups, rows, tokens].
        def lane_scales(scale):
            x = scale[page_table]  # [B, pages, BS, G]
            x = jnp.pad(x, (
                (0, 0), (0, groups * group - num_pages), (0, 0), (0, 0),
            )).reshape(B, groups, group * block_size, kv_heads)
            return x.transpose(0, 1, 3, 2)[:, :, np.arange(rows) % kv_heads]

        in_specs += [pl.BlockSpec(
            (1, groups, rows, group * block_size),
            lambda b, tbl, lens: (b, 0, 0, 0),
        )] * 2
        operands += [lane_scales(scale_k), lane_scales(scale_v)]
    # The pools stay where they lie: the kernel copies a lane's live pages
    # through the prefetched table.
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
    operands += [pool_k, pool_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, group, block_size, width), pool_k.dtype),
            pltpu.VMEM((2, group, block_size, width), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, num_rep, width), q.dtype),
        # One lane after another: a lane's last step fetches the next
        # lane's first pages.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name="paged_decode",
        interpret=interpret,
    )(page_table, seq_lens, *operands)
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
