"""Shared transformer backbone for GPT-2 / BERT / ViT (+ MoE variants).

One block implementation covers all three reference transformer workloads
(``BASELINE.json:9-11``) via flags: pre-LN (GPT-2, ViT) vs post-LN (BERT),
causal vs bidirectional attention, exact vs tanh-approx GELU, per-model LN
epsilon.

TPU-first design:
- weights carry logical axes: attention projections ('embed','heads','kv'),
  MLP ('embed','mlp') — so Megatron TP = the rules table mapping heads/mlp
  to the 'tp' mesh axis, with XLA inserting the block-boundary collectives;
- activations are constrained to ('batch','seq','embed') between blocks
  (sequence dim on 'cp' enables ring/Ulysses context parallelism);
- attention softmax in fp32 regardless of compute dtype (bf16-safe);
- block names are pinned so remat cannot perturb param-init RNG paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..comms import identity_fwd_psum_bwd, psum_identity_bwd
from ..comms_quant import block_quantize
from ..sharding import constrain

Dtype = jnp.dtype


def gelu_exact(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(x.dtype)))


def gelu_tanh(x):
    # GPT-2's "gelu_new".
    return nn.gelu(x, approximate=True)


def dense_init(scale: float = 0.02):
    return nn.initializers.normal(stddev=scale)


def attention_core(
    q,
    k,
    v,
    *,
    impl: str,
    causal: bool,
    dtype,
    mesh=None,
    mask=None,
    kv_valid=None,
    head_axes=None,
    dropout=None,
):
    """Post-projection attention dispatch — the ONE place the xla, fused
    flash, and ring cores are selected (shared by ``SelfAttention`` and
    ``models/llama.LlamaAttention``, so a core numerics fix lands once).

    q/k/v: [batch, seq, heads, head_dim] with equal head counts (GQA is
    repeated to MHA by the caller). ``mask``/``dropout`` apply to the xla
    core only (callers gate the other cores loudly); ``kv_valid`` and
    ``head_axes`` are flash-kernel options; ``mesh`` is required by the
    ring cores.
    """
    if impl == "flash":
        from ..ops import flash_attention

        return flash_attention(
            q, k, v, causal=causal, kv_valid_lens=kv_valid,
            **({"head_axes": head_axes} if head_axes else {}),
        )
    if impl in ("ring", "ring_pallas"):
        if mesh is None:
            raise ValueError(f"attn_impl={impl!r} requires mesh")
        from ..parallel.sp_ring import ring_attention_fn

        return ring_attention_fn(impl)(q, k, v, mesh, causal=causal)
    if impl != "xla":
        raise ValueError(f"unknown attn_impl {impl!r}")
    head_dim = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(head_dim)
    if causal:
        q_len, k_len = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), bool))
        scores = jnp.where(causal_mask[None, None], scores, -1e30)
    if mask is not None:
        # mask: [batch, k_len] (1 = attend) or broadcastable.
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        scores = jnp.where(mask.astype(bool), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if dropout is not None:
        probs = dropout(probs)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _cache_attend(q, ck, cv, visible, num_rep: int, dtype):
    """Attend q [B, L, H, D] against a full cached k/v [B, K, kv_heads, D]
    under a [B, L, K] visibility mask — the ONE cached-attention core shared
    by the contiguous decode cache and the paged serving cache.

    ``num_rep > 1`` (GQA): contract each query-head group directly against
    the UN-repeated cache — materializing a repeated cache every step would
    transiently re-spend the exact HBM the pre-repeat cache saves. Same math
    as the xla core on repeated heads (repeat is group-major: query head
    g*rep+r reads kv group g).
    """
    B, L, H, D = q.shape
    if num_rep > 1:
        kv_heads = ck.shape[2]
        qg = q.reshape(B, L, kv_heads, num_rep, D)
        scores = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qg, ck
        ).astype(jnp.float32) / np.sqrt(D)
        scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, cv)
        return out.reshape(B, L, H, D)
    return attention_core(
        q, ck, cv, impl="xla", causal=False,
        dtype=dtype, mask=visible[:, None, :, :],
    )


def window_ring_blocks(window: int, block_size: int) -> int:
    """Blocks a window layer keeps of one lane: ``window`` consecutive
    positions touch at most ``ceil(window / block_size) + 1`` blocks (the
    oldest and the newest both partly)."""
    return -(-int(window) // int(block_size)) + 1


def paged_decode_attention(module, q, k, v, *, dtype, kv_pages,
                           num_rep: int = 1, lens_var=None,
                           kernel: str = "reference",
                           kv_quant: str = "off",
                           window: int | None = None,
                           window_blocks: int = 0):
    """Decode/prefill attention against a PAGED KV cache (serving engine).

    Instead of one contiguous [B, max_len] cache per sequence, k/v live in a
    fixed **block pool** shared by all in-flight sequences — ``kv_pages =
    (num_blocks, block_size, pages_per_seq)``:

    - ``pool_key`` / ``pool_value``: [num_blocks, block_size, kv_heads*D],
      one pool per layer, batch-independent — the SAME pool arrays serve the
      B=1 prefill graph and the B=slots decode graph. Heads are folded
      into the minor dimension because of how the TPU tiles an array: a
      bf16 tile is (16, 128), so minor dims (kv_heads, D) = (12, 64) pad
      to (16, 128), 2.7x the bytes, and the runtime instead stores such
      a leaf with the BLOCK index minor-most — a layout no scatter or
      gather can use, so every program re-laid the whole pool out on
      every call (PERF.md §6, PR 25). kv_heads*D is a multiple of 128 at
      every served width (768, 1024, 512) and block_size=16 rows is one
      tile: no padding, the declared order is kept, a block is one
      contiguous run, and the scatter and the gather below take the
      array as it lies. Heads are split only on the gathered result;
    - ``page_table``: [B, pages_per_seq] int32 — row b's logical block j
      lives in physical pool block ``page_table[b, j]`` (allocation is
      host-side: serving/scheduler.KVBlockPool);
    - ``seq_lens``: [B] int32 — tokens already cached per row. This call's L
      tokens occupy logical positions ``seq_lens[b] .. seq_lens[b]+L-1``
      (per-ROW cursors, unlike the contiguous path's shared scalar — rows at
      different depths decode in one batch: continuous batching).

    The serving engine reserves pool block 0 as a NULL block: idle slots
    point their whole page table at it and keep ``seq_lens = 0``, so their
    (garbage) writes land harmlessly in block 0 and their attention output
    is discarded host-side.

    L == 1 is one decode step; L > 1 is bulk prefill — or, at small L, the
    serving engine's speculative **verify** forward (L = K+1 tokens per
    row starting at each row's OWN cursor, ``serving.speculation``): the
    per-row causal mask ``col <= seq_lens[b] + i`` gives query i exactly
    the prefix through its own draft token, so all K+1 greedy
    continuations come out of one call. Because write positions, the
    mask, and (model-side) absolute positions all chain off ``seq_lens``,
    bulk prefill at a NONZERO cursor is the prefix cache's suffix-only
    prefill (``serving.prefix_cache``): the row's table maps shared,
    published blocks below the cursor — read-only by construction, since
    every scatter index is ``>= seq_lens[b]`` and cached blocks only
    cover positions below it — and the suffix attends straight into the
    shared prefix KV through the same gather. Positions beyond a prompt's real
    length (prefill pad) or past a rejected draft write garbage KV into
    the row's own reserved pages — or the null block, past the
    reservation — and are overwritten in place by later writes at the
    same cursor positions before any query can attend them; causal
    masking hides them within the step that wrote them.

    ``kernel`` selects the read path (the engine passes what
    ``serving.engine.read_path`` chose: ``pallas`` for ``in_place``):
    - ``reference``: gather each row's pages into a contiguous
      [B, pages*bs] view and run ``_cache_attend`` — materializes the
      gathered cache per layer per step (the CPU-sim reference lowering);
    - ``pallas``: the fused ``ops/paged_attention`` kernel reads the pool
      IN PLACE via scalar-prefetch page-table indirection (interpret mode
      off-TPU, so parity is tested everywhere). Decode steps (L == 1)
      only: bulk prefill runs once per request and keeps the gather —
      the hot loop is the per-step decode. The speculative verify
      forward is L > 1 every step, so it would silently fall back to the
      gather here — the rule takes the gather for a speculating engine
      and a demanded ``speculation x attn_kernel='pallas'`` is fenced by
      name at config time until a multi-token kernel lands.

    The pool WRITE (scatter at the cursor) is the same XLA
    scatter-at-indices in both modes; only the read side differs.

    ``kv_quant='int8'`` (``serving.kv_quant``) stores the pool as int8
    with parallel f32 scale pools ``pool_key_scale``/``pool_value_scale``
    of shape [num_blocks, block_size, kv_heads]: ONE scale per written
    (token slot, kv head) D-vector, computed by ``comms_quant.
    block_quantize`` at scatter time with block_size=D — so each slot is
    quantized exactly once when its KV is written and never touched
    again (published-block immutability holds bitwise; a per-PAGE scale
    would have to re-quantize already-written slots as the page's absmax
    grew under progressive decode). The read path dequantizes: the
    reference kernel on the gathered pages (dequant-on-gather), the
    Pallas kernel inline in VMEM per page DMA (``ops/paged_attention``).
    Scale overhead is 4/D bytes per int8 KV byte (~6%% at D=64), so one
    fp32 pool block's budget holds ~3.8x more int8 tokens — the engine's
    sizing probe measures the real ratio. The scale pools keep their
    head-minor shape, which the TPU runtime stores block-index-minor and
    each program still re-lays out (that 6%% only): with the block index
    first, no shape is tile-dense for block_size*kv_heads scales a block
    (PERF.md §7).

    ``window`` (None: a global layer, everything above) makes this a
    **window layer**: query t sees keys j with ``t - window < j <= t``, in
    prefill and decode alike, and the layer keeps no more of a lane than
    its window. Its state is a second kind, beside the global layers':

    - ``pool_key_window`` / ``pool_value_window``: [``window_blocks`` (0: as
      many as ``num_blocks``), block_size, kv_heads*D], a pool of its own
      with its own null block 0, and ``page_table_window`` [B,
      pages_per_seq]: the lane's LOGICAL blocks, as ``page_table``, of
      which the host maps only the ``R = window_ring_blocks(window,
      block_size)`` that end at the cursor's block and points every other
      entry at the null block. The physical blocks behind them are a ring
      the host turns (``serving/engine.py``: logical block b of a lane
      that reserved n <= R blocks lives in its ``b % n``-th): a slot holds
      position p until the host maps the block of p + n * block_size onto
      it, which it does only once p has left every later query's window.
    - what is written where: this call's tokens scatter through the same
      ``_page_writes`` as a global layer's. A prompt position whose block
      has already left the window at the prompt's end, and a bucket's pad
      position past the prompt's last block, find the null block in the
      table, so neither can land on a live slot of the ring (two writes to
      one slot in one scatter have no order); pad positions inside the
      prompt's last block land in their own slots and are overwritten in
      place by decode before any query can see them, as in a global layer.
    - what is read: L == 1 gathers the R blocks that end at the cursor's
      (``window + block_size`` tokens a lane at most, whatever the lane's
      length) under the band; L > 1 reads nothing from the pool: the
      call's own tokens attend among themselves under the band, a chunk of
      queries against the ``window + chunk`` keys it can see. That is
      right at cursor 0 only: bulk prefill. What would start an L > 1 call
      at a cursor (prefix cache, speculation) is refused for a model with
      window layers at config time (``engine._check_window_cache``), and
      such a row's output is poisoned to NaN here.
    - ``kernel='pallas'`` and ``kv_quant='int8'`` are not built for it.

    Queries run ``_in_query_chunks`` on every gather path, so a bucket of P
    prompt tokens holds [heads, chunk, keys] float32 scores, not
    [heads, P, keys].
    """
    if window is not None and (kernel != "reference" or kv_quant != "off"):
        raise NotImplementedError(
            f"window layer x paged_kernel={kernel!r} / kv_quant="
            f"{kv_quant!r}: ops/paged_attention.py has no window in its "
            "mask and the int8 pool no ring — only the gather read path on "
            "an unquantized pool is built for window layers"
        )
    if kernel not in ("reference", "pallas"):
        raise ValueError(
            f"paged kernel must be 'reference' or 'pallas', got {kernel!r}"
        )
    if kv_quant not in ("off", "int8"):
        raise ValueError(
            f"kv_quant must be 'off' or 'int8', got {kv_quant!r}"
        )
    quantized = kv_quant == "int8"
    num_blocks, bs, pages = kv_pages
    kind = ""
    if window is not None:
        kind, num_blocks = "_window", int(window_blocks) or num_blocks
    B, L, Hkv, D = k.shape
    pk = module.variable(
        "cache", "pool_key" + kind, jnp.zeros, (num_blocks, bs, Hkv * D),
        jnp.int8 if quantized else k.dtype,
    )
    pv = module.variable(
        "cache", "pool_value" + kind, jnp.zeros, (num_blocks, bs, Hkv * D),
        jnp.int8 if quantized else v.dtype,
    )
    sk = sv = None
    if quantized:
        sk = module.variable(
            "cache", "pool_key_scale", jnp.zeros,
            (num_blocks, bs, Hkv), jnp.float32,
        )
        sv = module.variable(
            "cache", "pool_value_scale", jnp.zeros,
            (num_blocks, bs, Hkv), jnp.float32,
        )
    table = module.variable(
        "cache", "page_table" + kind,
        lambda: jnp.zeros((B, pages), jnp.int32),
    )
    lens = lens_var if lens_var is not None else module.variable(
        "cache", "seq_lens", lambda: jnp.zeros((B,), jnp.int32)
    )
    if module.is_initializing():
        # Shape-only pass: create the pool and run plain causal attention.
        def rep(t):
            return jnp.repeat(t, num_rep, axis=2) if num_rep > 1 else t

        return attention_core(
            q, rep(k), rep(v), impl="xla", causal=True, dtype=dtype
        )
    pos, flat = _page_writes(table.value, lens.value, L, bs)
    k_w, v_w = k, v
    if quantized:
        # Quantize-at-write: one comms_quant block per (token, head)
        # D-vector (block_size=D), so the scale for a slot is final the
        # moment its KV lands and scatters to the SAME flat index as the
        # int8 values.
        k_w, k_scale = block_quantize(
            k.astype(jnp.float32).reshape(-1), D
        )
        v_w, v_scale = block_quantize(
            v.astype(jnp.float32).reshape(-1), D
        )
        sk.value = sk.value.reshape(num_blocks * bs, Hkv).at[flat].set(
            k_scale.reshape(B * L, Hkv)
        ).reshape(sk.value.shape)
        sv.value = sv.value.reshape(num_blocks * bs, Hkv).at[flat].set(
            v_scale.reshape(B * L, Hkv)
        ).reshape(sv.value.shape)
    with jax.named_scope("kv_write"):
        pk.value = pk.value.reshape(num_blocks * bs, Hkv * D).at[flat].set(
            k_w.reshape(B * L, Hkv * D)
        ).reshape(pk.value.shape)
        pv.value = pv.value.reshape(num_blocks * bs, Hkv * D).at[flat].set(
            v_w.reshape(B * L, Hkv * D)
        ).reshape(pv.value.shape)
    if window is not None:
        with jax.named_scope("attn_window"):
            out = _window_attend(
                q, k, v, pk.value, pv.value, table.value, pos,
                window=window, bs=bs, num_rep=num_rep, dtype=dtype,
            )
        if L > 1:
            # Bulk prefill only (docstring): a row that starts at a cursor
            # would need what the pool holds below it.
            out = jnp.where(
                (lens.value != 0)[:, None, None, None], jnp.nan, out
            )
    elif kernel == "pallas" and L == 1:
        from ..ops.paged_attention import paged_attention

        out = paged_attention(
            q[:, 0], pk.value, pv.value, table.value, lens.value,
            num_rep=num_rep,
            scale_k=sk.value if quantized else None,
            scale_v=sv.value if quantized else None,
        )[:, None]
    else:
        # Gather each row's pages into logical order, whole lane-dense
        # blocks, and split heads on the result: [B, pages*bs, Hkv, D].
        ck = pk.value[table.value].reshape(B, pages * bs, Hkv, D)
        cv = pv.value[table.value].reshape(B, pages * bs, Hkv, D)
        if quantized:
            # Dequant-on-gather: the gathered int8 pages scale back to f32
            # against their gathered scale rows — the reference lowering's
            # mirror of the Pallas kernel's in-VMEM dequant.
            ck = ck.astype(jnp.float32) * sk.value[table.value].reshape(
                B, pages * bs, Hkv
            )[..., None]
            cv = cv.astype(jnp.float32) * sv.value[table.value].reshape(
                B, pages * bs, Hkv
            )[..., None]
        cols = jnp.arange(pages * bs)
        with jax.named_scope("attn_global"):
            out = _in_query_chunks(
                lambda qc, pc: _cache_attend(  # causal per row
                    qc, ck, cv, cols[None, None, :] <= pc[:, :, None],
                    num_rep, dtype,
                ),
                L, q, pos,
                chunk=_query_chunk(q.shape[2], pages * bs),
            )
    if jax.config.jax_enable_checks:
        # Debug-mode OOB tripwire (train.debug_checks): XLA clamps OOB
        # gather/scatter indices SILENTLY, so a corrupt page table reads —
        # and scatter-writes — the wrong physical block instead of
        # failing (same hazard models/gpt2.py guards in the embedding
        # path). Whether an entry is in range is data-dependent, so it
        # cannot raise under jit — poison the offending rows to NaN
        # instead (loud under debug_nans / any downstream check), the
        # flash non-prefix-mask idiom. The serving engine additionally
        # range-checks every host-built table before injection.
        bad = ((table.value < 0) | (table.value >= num_blocks)).any(axis=1)
        out = jnp.where(bad[:, None, None, None], jnp.nan, out)
    lens.value = lens.value + L
    return out


def _window_attend(q, k, v, pool_k, pool_v, table, pos, *, window: int,
                   bs: int, num_rep: int, dtype):
    """A window layer's read (``paged_decode_attention``'s docstring): one
    decode token against the R blocks of the ring that end at its own, or
    a prompt's tokens among themselves; both under the band ``t - window <
    j <= t``."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    if L == 1:
        R = window_ring_blocks(window, bs)
        # Logical blocks cur - R + 1 .. cur of every row; the ones before
        # the lane's first are masked below, whatever the clip reads.
        logical = pos // bs - (R - 1) + jnp.arange(R)[None, :]  # [B, R]
        phys = jnp.take_along_axis(
            table, jnp.clip(logical, 0, table.shape[1] - 1), axis=1
        )
        ck = pool_k[phys].reshape(B, R * bs, Hkv, D)
        cv = pool_v[phys].reshape(B, R * bs, Hkv, D)
        cols = (
            logical[:, :, None] * bs + jnp.arange(bs)[None, None, :]
        ).reshape(B, 1, R * bs)
        t = pos[:, :, None]
        visible = (cols <= t) & (cols > t - window) & (cols >= 0)
        return _cache_attend(q, ck, cv, visible, num_rep, dtype)
    chunk = _query_chunk(H, min(L, window + _LATENT_QUERY_CHUNK))
    keys = min(L, window + chunk)  # what one chunk of queries can see

    def attend(qc, rel):
        # rel [B, C]: the queries' places in this call, the same in every
        # row; their keys lie in [last - keys + 1, last].
        start = jnp.clip(rel[0, -1] + 1 - keys, 0, L - keys)
        kc = jax.lax.dynamic_slice_in_dim(k, start, keys, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, keys, axis=1)
        cols = (start + jnp.arange(keys))[None, None, :]
        t = rel[:, :, None]
        return _cache_attend(
            qc, kc, vc, (cols <= t) & (cols > t - window), num_rep, dtype
        )

    return _in_query_chunks(
        attend, L, q, jnp.broadcast_to(jnp.arange(L), (B, L)), chunk=chunk
    )


def _page_writes(table, lens, L: int, bs: int):
    """Where this call's L tokens of every row land: their absolute
    positions [B, L] and flat slot indices ``block * bs + offset`` [B*L]
    into a pool viewed as [num_blocks * bs, width]."""
    pos = lens[:, None] + jnp.arange(L)[None, :]
    blk = jnp.take_along_axis(table, pos // bs, axis=1)
    return pos, (blk * bs + pos % bs).reshape(-1)


# Queries attended at once by the gather paths: a bucket of P prompt
# tokens never holds more than [heads, chunk, keys] float32 scores.
_LATENT_QUERY_CHUNK = 512
_SCORE_BYTES = 1 << 30


def _query_chunk(heads: int, keys: int) -> int:
    """The largest power of two from 64 to ``_LATENT_QUERY_CHUNK`` whose
    [heads, chunk, keys] float32 scores stay within ``_SCORE_BYTES`` (128
    at 128 heads over 13,312 keys; 512 at every GPT-2 and GLM shape)."""
    chunk = _LATENT_QUERY_CHUNK
    while chunk > 64 and heads * chunk * keys * 4 > _SCORE_BYTES:
        chunk //= 2
    return chunk


def _in_query_chunks(fn, L: int, *per_query, chunk: int = _LATENT_QUERY_CHUNK):
    """``fn`` over ``per_query`` arrays ([B, L, ...]) a chunk of queries
    at a time where L is long (results concatenated along L; what is left
    over after whole chunks is one more call); one call where it is
    short."""
    if L <= chunk:
        return fn(*per_query)
    n = L // chunk
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a[:, :n * chunk].reshape(a.shape[0], n, chunk, *a.shape[2:]), 1, 0
    )
    out = jax.lax.map(lambda xs: fn(*xs), tuple(split(a) for a in per_query))
    out = jnp.moveaxis(out, 0, 1)
    out = out.reshape(out.shape[0], n * chunk, *out.shape[3:])
    if L % chunk:
        out = jnp.concatenate(
            [out, fn(*(a[:, n * chunk:] for a in per_query))], axis=1
        )
    return out


def latent_paged_attention(module, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv,
                           *, dtype, kv_pages, lens_var):
    """Multi-head LATENT attention (DeepSeek-V2/V3 MLA) against the paged
    pool: the cache holds, per token and layer, the normed latent
    ``c_kv`` [rank] and the one shared RoPE key ``k_rope`` [rope] — not
    per-head K and V — so a token costs rank + rope values a layer
    whatever the head count (576 against 20 x 512 at GLM-4.7-Flash).

    - ``pool_latent``: [num_blocks, block_size, W], one leaf a layer
      holding ``[c_kv | k_rope | 0...]``, written by the same
      scatter-at-cursor and read by the same whole-block page gather as
      ``paged_decode_attention``'s K and V (same ``page_table`` /
      ``seq_lens`` leaves, same null block, same per-row cursors), shared
      between the B=1 prefill and the B=slots decode programs and found by
      name in the engine (``engine._POOL_LEAVES``). W is rank + rope
      rounded up to whole lanes of 128 (576 -> 640 at GLM-4.7-Flash, 11%
      of the pool): in a deviceless ``v5e:2x2`` compile of the engine's
      programs a leaf of 576 is stored block-index-minor (``{0,2,1}``) and
      copied whole twice a call, as PR 25's K and V were, and so is the
      64-wide half of a ``[.., 512]`` + ``[.., 64]`` pair; the leaf of 640
      keeps its declared order and the decode program copies none
      (``tests/test_tpu_compile.py`` keeps that count).
    - ``q_nope`` [B, L, H, nope], ``q_rope`` [B, L, H, rope] (rotated),
      ``c_kv`` [B, L, rank], ``k_rope`` [B, L, rope] (rotated);
      ``w_uk`` [rank, H, nope] and ``w_uv`` [rank, H, v] are the two
      halves of the per-head up-projection ``kv_b``.

    Two read forms, the same mathematics (scores over nope + rope dims):

    - **expanded** — K and V of this call's own tokens are expanded per
      head (``k_nope = c_kv w_uk``, ``v = c_kv w_uv``) and attended
      causally among themselves; nothing is read from the pool. Right for
      a prompt that starts at cursor 0 (bulk prefill), where it costs
      2 x (nope + rope + v) FLOPs a (query, key, head) against the
      absorbed form's 2 x (2 rank + rope).
    - **absorbed** — ``w_uk`` is folded into the query
      (``q_lat = q_nope w_uk^T`` [rank]) and ``w_uv`` applied after the
      weighted sum (``o = (softmax c_kv) w_uv``), so the latent is read as
      it lies, as one shared key/value head: right for decode (L == 1) and
      for L > 1 at a nonzero cursor (the speculative verify forward,
      suffix-only prefill under the prefix cache), which must see what the
      pool holds below the cursor.

    L == 1 compiles the absorbed form alone. L > 1 compiles both and picks
    by the cursors at run time (all rows at 0: expanded), so one prefill
    executable per bucket serves cold and suffix-only prefill alike, as
    ``paged_decode_attention``'s does. Returns [B, L, H, v].
    """
    num_blocks, bs, pages = kv_pages
    B, L, H, _ = q_nope.shape
    rank, rope = c_kv.shape[-1], k_rope.shape[-1]
    width = -(-(rank + rope) // 128) * 128  # whole lanes (docstring)
    pool = module.variable(
        "cache", "pool_latent", jnp.zeros, (num_blocks, bs, width),
        c_kv.dtype,
    )
    table = module.variable(
        "cache", "page_table", lambda: jnp.zeros((B, pages), jnp.int32)
    )
    scale = 1.0 / np.sqrt(q_nope.shape[-1] + rope)

    def attend(q_a, k_a, q_b, k_b, values, visible):
        """softmax((q_a . k_a + q_b . k_b) * scale) values; k_b is one key
        head shared by all query heads, k_a and values per head (4 dims)
        or shared (3 dims)."""
        per_head = "bkhe" if k_a.ndim == 4 else "bke"
        scores = (
            jnp.einsum(f"bqhe,{per_head}->bhqk", q_a, k_a)
            + jnp.einsum("bqhe,bke->bhqk", q_b, k_b)
        ).astype(jnp.float32) * scale
        scores = jnp.where(visible[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum(f"bhqk,{per_head}->bqhe", probs, values)

    def expanded():
        k_nope = jnp.einsum("bkr,rhe->bkhe", c_kv, w_uk)
        v = jnp.einsum("bkr,rhe->bkhe", c_kv, w_uv)
        cols = jnp.arange(L)[None, None, :]
        return _in_query_chunks(
            lambda qn, qr, qpos: attend(
                qn, k_nope, qr, k_rope, v, cols <= qpos[:, :, None]
            ),
            L, q_nope, q_rope, jnp.broadcast_to(jnp.arange(L), (B, L)),
        )

    if module.is_initializing():
        # Shape-only pass: create the pool and attend this call's tokens.
        return expanded()
    pos, flat = _page_writes(table.value, lens_var.value, L, bs)
    with jax.named_scope("latent_write"):
        row = jnp.concatenate([
            c_kv, k_rope,
            jnp.zeros((B, L, width - rank - rope), c_kv.dtype),
        ], axis=-1)
        pool.value = pool.value.reshape(num_blocks * bs, width).at[
            flat
        ].set(row.reshape(B * L, width)).reshape(pool.value.shape)

    def absorbed():
        # Gather each row's pages into logical order, whole blocks:
        # [B, pages*bs, W]; the slice at ``rank`` is lane-aligned.
        lat = pool.value[table.value].reshape(B, pages * bs, width)
        ck, kr = lat[..., :rank], lat[..., rank:rank + rope]
        cols = jnp.arange(pages * bs)[None, None, :]

        def chunk(qn, qr, qpos):
            q_lat = jnp.einsum("bqhe,rhe->bqhr", qn, w_uk)
            o_lat = attend(q_lat, ck, qr, kr, ck, cols <= qpos[:, :, None])
            return jnp.einsum("bqhr,rhe->bqhe", o_lat, w_uv)

        return _in_query_chunks(chunk, L, q_nope, q_rope, pos)

    with jax.named_scope("mla_attend"):
        if L == 1:
            out = absorbed()
        else:
            out = jax.lax.cond(
                jnp.all(lens_var.value == 0), expanded, absorbed
            )
    if jax.config.jax_enable_checks:
        # The OOB tripwire of paged_decode_attention (its comment).
        bad = ((table.value < 0) | (table.value >= num_blocks)).any(axis=1)
        out = jnp.where(bad[:, None, None, None], jnp.nan, out)
    lens_var.value = lens_var.value + L
    return out


def decode_attention(module, q, k, v, *, dtype, attn_impl="xla",
                     idx_var=None, num_rep: int = 1, start_var=None):
    """One autoregressive decode step against a KV cache (used by
    ``SelfAttention`` and ``models/llama.LlamaAttention`` when
    ``decode=True``; driven by ``generate.py``).

    The cache lives in the module's ``'cache'`` variable collection:
    ``cached_key``/``cached_value`` sized by the INIT call's sequence length
    (= the total generation budget) and a ``cache_index`` cursor. Real
    calls feed one token: its k/v are written at the cursor, q attends over
    the visible prefix, the cursor advances.

    ``num_rep`` (GQA): k/v arrive PRE-repeat ([B, L, kv_heads, D]) and are
    cached that way — the cache is ``num_heads/num_kv_heads`` times smaller
    than the query head count implies (ADVICE r3 #4: caching the repeated
    kv erodes GQA's memory benefit); the repeat happens per step at use.

    Left-padded batches: a per-row ``start`` cache variable ([B], default
    0 = pad-free) hides columns before each row's first real token, so
    ``generate(prompt_lens=...)`` can batch uneven prompts (HF left-padding
    semantics).
    """
    if attn_impl != "xla":
        raise NotImplementedError(
            f"decode supports attn_impl='xla' only, got {attn_impl!r} "
            "(the fused kernels have no incremental path)"
        )
    ck = module.variable("cache", "cached_key", jnp.zeros, k.shape, k.dtype)
    cv = module.variable("cache", "cached_value", jnp.zeros, v.shape, v.dtype)
    # A compact module may only register a name once — callers that read
    # the cursor themselves (Llama's RoPE offset) pass it in.
    idx = idx_var if idx_var is not None else module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )
    start = start_var if start_var is not None else module.variable(
        "cache", "start", lambda: jnp.zeros((k.shape[0],), jnp.int32)
    )

    def rep(t):
        return jnp.repeat(t, num_rep, axis=2) if num_rep > 1 else t

    if module.is_initializing():
        # Shape-only pass: create the cache at this call's length and run
        # plain causal attention so init produces valid outputs.
        return attention_core(
            q, rep(k), rep(v), impl="xla", causal=True, dtype=dtype
        )
    B, L, H, D = q.shape
    # L == 1: one decode step. L > 1: BULK PREFILL — the whole prompt is
    # cached and attended in one forward (L sequential steps of tiny
    # matmuls would waste the MXU; generate.py's prefill path feeds the
    # prompt here in one call). Query t sits at absolute position idx + t.
    ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, idx.value, 0, 0))
    cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, idx.value, 0, 0))
    max_len = ck.value.shape[1]
    cols = jnp.arange(max_len)
    qpos = idx.value + jnp.arange(L)
    # [B, L, max_len]: causal within the written prefix, pad columns (< a
    # row's start) never visible.
    visible = (
        (cols[None, None, :] <= qpos[None, :, None])
        & (cols[None, None, :] >= start.value[:, None, None])
    )
    out = _cache_attend(q, ck.value, cv.value, visible, num_rep, dtype)
    idx.value = idx.value + L
    return out


class SelfAttention(nn.Module):
    """Multi-head self-attention with logical-axis-annotated projections.

    ``attn_impl`` selects the attention core (SURVEY.md §2b/§5):
    - ``xla``:     einsum + softmax, fused by XLA (default);
    - ``ulysses``: same core, but q/k/v are constrained to the
                   seq-gathered/head-sharded layout so the partitioner emits
                   the Ulysses all-to-alls around it (``cp`` mesh axis);
    - ``ulysses_flash``: Ulysses reshard around the fused Pallas flash
                   kernel (sharded over heads on ``(tp, cp)`` inside);
                   mask=None, dropout=0 only;
    - ``ring``:    explicit shard_map ring attention over ``cp`` with
                   ppermute KV rotation (``ops/ring_attention.py``); needs
                   ``mesh`` and supports mask=None, dropout=0 only;
    - ``ring_pallas``: same ring, per-visit block attention fused into a
                   Pallas kernel (``ops/ring_attention_pallas.py``); same
                   constraints as ``ring``;
    - ``flash``:   fused Pallas flash-attention kernel
                   (``ops/flash_attention.py``); supports mask=None or a
                   [batch, k_len] contiguous-prefix key-padding mask
                   (non-prefix masks poison the output to NaN); no active
                   attention-dropout.
    """

    num_heads: int
    head_dim: int
    causal: bool = False
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.float32
    init_scale: float = 0.02
    attn_impl: str = "xla"  # xla | ulysses | ring | ring_pallas | flash
    mesh: object = None  # jax.sharding.Mesh, required for ring variants
    # Manual tensor parallelism (inside an explicit shard_map, e.g. PP×TP):
    # this module then sees tp-LOCAL head counts and psums the row-parallel
    # out-projection over this axis. The out bias must be pre-scaled 1/tp by
    # the caller (it is added per-rank before the psum).
    psum_axis: str | None = None
    # Megatron f/g markers for MANUALLY-differentiated engines (jax.vjp
    # inside shard_map(check_vma=False), e.g. interleaved 1F1B): the entry
    # marker must NOT run under outer-autodiff paths, whose shard_map
    # transpose already inserts the reduction (enabling both would double
    # the input-cotangent).
    manual_tp_ad: bool = False
    # Autoregressive decoding with a KV cache (generate.py): the module
    # keeps cached_key/cached_value/cache_index in the 'cache' collection.
    # The init call (any length) only shapes the cache; real calls then
    # feed ONE token at a time. attn_impl='xla' only.
    decode: bool = False
    # Serving engine (serving/engine.py): with decode=True, a non-None
    # (num_blocks, block_size, pages_per_seq) switches the cache to the
    # PAGED block-pool layout with per-row cursors (paged_decode_attention)
    # instead of the contiguous per-sequence cache.
    kv_pages: tuple | None = None
    # Paged read path: 'reference' (gather) or 'pallas' (in-place fused
    # kernel, ops/paged_attention.py), as serving.engine.read_path chose.
    paged_kernel: str = "reference"
    # Paged pool storage: 'off' (model dtype) or 'int8' (quantize at
    # scatter, dequant on read; scale pools ride in the cache) —
    # serving.kv_quant (paged_decode_attention).
    kv_quant: str = "off"

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        features = x.shape[-1]
        if self.psum_axis is not None and self.manual_tp_ad:
            # Megatron f: entry of the tensor-parallel region (conjugate of
            # the psum_identity_bwd at its exit) — the input cotangent is
            # the SUM of the per-rank head-slice contributions.
            x = identity_fwd_psum_bwd(x, self.psum_axis)
        proj = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(self.num_heads, self.head_dim),
            dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(self.init_scale), ("embed", "heads", "kv")
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("heads", "kv")
            ),
            name=name,
        )
        q = proj("query")(x)
        k = proj("key")(x)
        v = proj("value")(x)

        if self.decode:
            if mask is not None:
                raise NotImplementedError(
                    "decode ignores key-padding masks — pad-free prompts "
                    "only (the cache visibility mask is cursor-based)"
                )
            if self.kv_pages is not None:
                if self.attn_impl != "xla":
                    raise NotImplementedError(
                        "paged decode supports attn_impl='xla' only, got "
                        f"{self.attn_impl!r}"
                    )
                out = paged_decode_attention(
                    self, q, k, v, dtype=self.dtype, kv_pages=self.kv_pages,
                    kernel=self.paged_kernel, kv_quant=self.kv_quant,
                )
            else:
                out = decode_attention(self, q, k, v, dtype=self.dtype,
                                       attn_impl=self.attn_impl)
        elif self.attn_impl == "flash":
            if self.dropout_rate and not deterministic:
                raise NotImplementedError(
                    "flash attention supports no active attention-dropout"
                )
            kv_valid = None
            not_prefix = None
            if mask is not None:
                if mask.ndim != 2:
                    raise NotImplementedError(
                        "flash attention supports key-padding masks "
                        "([batch, k_len] contiguous prefix) or mask=None"
                    )
                # Contiguous-prefix padding mask -> per-sequence kv limit.
                # Whether a mask IS a prefix is data-dependent, so it cannot
                # raise under jit — instead non-prefix rows are poisoned to
                # NaN below: loud (debug_nans / NaN loss) rather than
                # silently attending to the wrong columns.
                kv_valid = mask.astype(jnp.int32).sum(-1)
                prefix = jnp.arange(mask.shape[-1])[None, :] < kv_valid[:, None]
                not_prefix = (mask.astype(bool) != prefix).any(-1)
            out = attention_core(
                q, k, v, impl="flash", causal=self.causal,
                dtype=self.dtype, kv_valid=kv_valid,
            )
            if not_prefix is not None:
                out = jnp.where(
                    not_prefix[:, None, None, None], jnp.nan, out
                )
        elif self.attn_impl in ("ring", "ring_pallas"):
            if mask is not None or (self.dropout_rate and not deterministic):
                raise NotImplementedError(
                    "ring attention supports mask=None and no active "
                    "attention-dropout"
                )
            out = attention_core(
                q, k, v, impl=self.attn_impl, causal=self.causal,
                dtype=self.dtype, mesh=self.mesh,
            )
        elif self.attn_impl in ("ulysses", "ulysses_flash"):
            flash = self.attn_impl == "ulysses_flash"
            if flash and (
                mask is not None or (self.dropout_rate and not deterministic)
            ):
                raise NotImplementedError(
                    "ulysses_flash supports mask=None and no active "
                    "attention-dropout"
                )
            from ..parallel.sp_ulysses import ulysses_attention

            out = ulysses_attention(
                q, k, v, flash=flash, causal=self.causal, dtype=self.dtype,
                mesh=self.mesh, num_heads=self.num_heads,
                mask=None if flash else mask,
                dropout=None if flash else nn.Dropout(
                    self.dropout_rate, deterministic=deterministic
                ),
            )
        elif self.attn_impl == "xla":
            out = attention_core(
                q, k, v, impl="xla", causal=self.causal,
                dtype=self.dtype, mask=mask,
                dropout=nn.Dropout(
                    self.dropout_rate, deterministic=deterministic
                ),
            )
        else:
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        out = nn.DenseGeneral(
            features=features,
            axis=(-2, -1),
            dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(self.init_scale), ("heads", "kv", "embed")
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)
            ),
            name="out",
        )(out)
        if self.psum_axis is not None:
            out = psum_identity_bwd(out, self.psum_axis)
        return out


class Mlp(nn.Module):
    hidden_dim: int
    activation: str = "gelu_exact"  # gelu_exact | gelu_tanh
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.float32
    init_scale: float = 0.02
    # Manual TP (see SelfAttention.psum_axis): hidden_dim is tp-local and
    # fc_out is the row-parallel matmul reduced here; fc_out bias must be
    # pre-scaled 1/tp by the caller.
    psum_axis: str | None = None
    manual_tp_ad: bool = False  # see SelfAttention.manual_tp_ad

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        features = x.shape[-1]
        act = {"gelu_exact": gelu_exact, "gelu_tanh": gelu_tanh}[self.activation]
        if self.psum_axis is not None and self.manual_tp_ad:
            # Megatron f (see SelfAttention): entry of the parallel region.
            x = identity_fwd_psum_bwd(x, self.psum_axis)
        h = nn.Dense(
            self.hidden_dim,
            dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(self.init_scale), ("embed", "mlp")
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("mlp",)),
            name="fc_in",
        )(x)
        h = act(h)
        h = nn.Dense(
            features,
            dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(self.init_scale), ("mlp", "embed")
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)
            ),
            name="fc_out",
        )(h)
        if self.psum_axis is not None:
            h = psum_identity_bwd(h, self.psum_axis)
        return nn.Dropout(self.dropout_rate, deterministic=deterministic)(h)


def layer_norm(eps: float, dtype, name: str):
    return nn.LayerNorm(
        epsilon=eps,
        dtype=dtype,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("norm",)),
        name=name,
    )


class TransformerBlock(nn.Module):
    num_heads: int
    head_dim: int
    mlp_dim: int
    pre_ln: bool = True
    causal: bool = False
    activation: str = "gelu_exact"
    ln_eps: float = 1e-5
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.float32
    init_scale: float = 0.02
    attn_impl: str = "xla"
    mesh: object = None
    # Pipeline stages run inside an explicit shard_map where global sharding
    # constraints are meaningless — they disable the block-boundary constraint.
    constrain_out: bool = True
    # Manual TP inside shard_map (PP×TP): forwarded to the attn/mlp modules.
    psum_axis: str | None = None
    manual_tp_ad: bool = False  # see SelfAttention.manual_tp_ad
    decode: bool = False  # KV-cache decoding (see SelfAttention.decode)
    kv_pages: tuple | None = None  # paged serving cache (SelfAttention)
    paged_kernel: str = "reference"  # paged read path (SelfAttention)
    kv_quant: str = "off"  # paged pool storage codec (SelfAttention)

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        attn = SelfAttention(
            self.num_heads,
            self.head_dim,
            causal=self.causal,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            init_scale=self.init_scale,
            attn_impl=self.attn_impl,
            mesh=self.mesh,
            psum_axis=self.psum_axis,
            manual_tp_ad=self.manual_tp_ad,
            decode=self.decode,
            kv_pages=self.kv_pages,
            paged_kernel=self.paged_kernel,
            kv_quant=self.kv_quant,
            name="attn",
        )
        mlp = Mlp(
            self.mlp_dim,
            activation=self.activation,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            init_scale=self.init_scale,
            psum_axis=self.psum_axis,
            manual_tp_ad=self.manual_tp_ad,
            name="mlp",
        )
        ln1 = layer_norm(self.ln_eps, self.dtype, "ln1")
        ln2 = layer_norm(self.ln_eps, self.dtype, "ln2")
        drop = nn.Dropout(self.dropout_rate, deterministic=deterministic)

        if self.pre_ln:  # GPT-2 / ViT
            x = x + drop(attn(ln1(x), mask, deterministic))
            x = x + mlp(ln2(x), deterministic)
        else:  # BERT
            x = ln1(x + drop(attn(x, mask, deterministic)))
            x = ln2(x + mlp(x, deterministic))
        if not self.constrain_out:
            return x
        return constrain(x, "batch", "seq", "embed")


class TransformerStack(nn.Module):
    """N identically-configured blocks with pinned names and optional remat."""

    num_layers: int
    num_heads: int
    head_dim: int
    mlp_dim: int
    pre_ln: bool = True
    causal: bool = False
    activation: str = "gelu_exact"
    ln_eps: float = 1e-5
    dropout_rate: float = 0.0
    remat: str = "none"
    dtype: Dtype = jnp.float32
    init_scale: float = 0.02
    attn_impl: str = "xla"
    mesh: object = None
    decode: bool = False  # KV-cache decoding (see SelfAttention.decode)
    kv_pages: tuple | None = None  # paged serving cache (SelfAttention)
    paged_kernel: str = "reference"  # paged read path (SelfAttention)
    kv_quant: str = "off"  # paged pool storage codec (SelfAttention)

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        block_cls = TransformerBlock
        if self.remat != "none":
            policy = {
                "full": None,
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[self.remat]
            block_cls = nn.remat(
                block_cls, static_argnums=(3,), policy=policy
            )
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads,
                self.head_dim,
                self.mlp_dim,
                pre_ln=self.pre_ln,
                causal=self.causal,
                activation=self.activation,
                ln_eps=self.ln_eps,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                init_scale=self.init_scale,
                attn_impl=self.attn_impl,
                mesh=self.mesh,
                decode=self.decode,
                kv_pages=self.kv_pages,
                paged_kernel=self.paged_kernel,
                kv_quant=self.kv_quant,
                name=f"block_{i}",
            )(x, mask, deterministic)
        return x
