"""Fused flash attention — Pallas Mosaic kernel for the TPU MXU.

This is the TPU-native equivalent of the reference's fused "CUDA
forward/backward kernels" for attention (``BASELINE.json:5``): one kernel
computes the whole softmax(QK^T)V block-by-block in VMEM with the
online-softmax recurrence, so the [seq, seq] score matrix never
materializes in HBM. The backward pass is the standard two-kernel
recomputation scheme (dQ by query blocks, dK/dV by key blocks) wired up
as a ``jax.custom_vjp``.

Layout notes (see pallas_guide.md):
- grid is ``(batch*heads, q_blocks, kv_blocks)`` — the innermost grid
  dimension is sequential on TPU, so the online-softmax carries (m, l,
  acc) live in VMEM scratch across kv iterations;
- m/l scratch is ``(block_q, 128)`` (lane-width broadcast) to respect
  the fp32 (8, 128) tile;
- all accumulation is fp32 regardless of input dtype; ``jnp.dot`` with
  ``preferred_element_type=jnp.float32`` targets the MXU;
- causal blocks entirely above the diagonal are skipped with
  ``pl.when`` (no MXU work issued), the diagonal block is masked with
  ``broadcasted_iota``;
- on CPU backends the kernel runs in interpret mode, which is how the
  unit tests exercise it without a TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..mesh import BATCH_AXES

_NEG_INF = -1e30  # finite: exp(_NEG_INF - m) == 0 exactly, no inf-inf NaNs
_LANES = 128


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _blk(seq: int, requested: int, name: str) -> int:
    blk = min(requested, seq)
    if seq % blk:
        raise ValueError(f"{name}: seq={seq} not divisible by block={blk}")
    return blk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _col_mask(s, ki, block_q, block_k, limit):
    """Mask scores whose GLOBAL kv column index >= limit (static or traced)."""
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(col < limit, s, _NEG_INF)


def _fwd_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, causal, block_q, block_k, num_kv, valid_len=None,
    use_vl=False,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: skip kv blocks entirely above the diagonal.
    visible = (
        qi * block_q + block_q - 1 >= ki * block_k if causal else True
    )

    @pl.when(visible)
    def _block():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(row >= col, s, _NEG_INF)
        elif valid_len is not None:
            # Sequence was right-padded to a block multiple (valid_len is the
            # true length, a compile-time constant): padded kv columns must
            # not contribute. Padded q rows produce garbage rows the wrapper
            # slices away. Under causal the diagonal mask already excludes
            # every padded column for valid rows.
            s = _col_mask(s, ki, block_q, block_k, valid_len)
        if use_vl:
            # Per-sequence key-padding limit (runtime, SMEM): columns at or
            # beyond this batch element's valid length never contribute.
            s = _col_mask(s, ki, block_q, block_k, vl_ref[pl.program_id(0)])
        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _fwd(q, k, v, vl, causal, sm_scale, block_q, block_k, interpret,
         valid_len=None, use_vl=False):
    """q/k/v: [bh, seq, d]; vl: [bh] int32 per-row kv limits (used when
    ``use_vl``) -> (o [bh, seq, d], lse [bh, seq] fp32)."""
    bh, seq, d = q.shape
    block_q = _blk(seq, block_q, "flash fwd q")
    block_k = _blk(seq, block_k, "flash fwd k")
    num_q, num_kv = seq // block_q, seq // block_k
    grid = (bh, num_q, num_kv)
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kv=num_kv,
        valid_len=valid_len, use_vl=use_vl,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # vl
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            # Trailing singleton lane dim: satisfies Mosaic's tiling rule
            # (last block dim == array dim) without the 128x lane-broadcast
            # a (bh, seq) layout would force on this residual.
            jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(vl, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p(
    q_ref, k_ref, lse_ref, sm_scale, causal, qi, ki, bq, bk, valid_len=None,
    vl_ref=None, use_vl=False,
):
    """exp(scale*QK^T - lse) for one (q-block, kv-block) tile, fp32."""
    q = q_ref[0].astype(jnp.float32) * sm_scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if causal:
        row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(row >= col, s, _NEG_INF)
    elif valid_len is not None:
        s = _col_mask(s, ki, bq, bk, valid_len)
    if use_vl:
        s = _col_mask(s, ki, bq, bk, vl_ref[pl.program_id(0)])
    return jnp.exp(s - lse_ref[0])  # lse block is (bq, 1); masked -> 0


def _delta(o_ref, do_ref):
    """delta_i = sum_d dO_id O_id for one q block -> (bq, 1) fp32."""
    return jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True,
    )


def _dq_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
    dq_scr, delta_scr,
    *, sm_scale, causal, block_q, block_k, num_kv, valid_len=None,
    use_vl=False,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta depends only on the q block — compute once per kv sweep.
        delta_scr[:] = jnp.broadcast_to(_delta(o_ref, do_ref), delta_scr.shape)

    visible = (
        qi * block_q + block_q - 1 >= ki * block_k if causal else True
    )

    @pl.when(visible)
    def _block():
        p = _recompute_p(
            q_ref, k_ref, lse_ref, sm_scale, causal, qi, ki,
            block_q, block_k, valid_len, vl_ref, use_vl,
        )
        do = do_ref[0].astype(jnp.float32)  # (bq, d)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (bq, bk)
        ds = p * (dp - delta_scr[:, :1])
        dq_scr[:] += sm_scale * jnp.dot(
            ds, k_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_k, num_q, valid_len=None,
    use_vl=False,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    visible = (
        qi * block_q + block_q - 1 >= ki * block_k if causal else True
    )

    @pl.when(visible)
    def _block():
        p = _recompute_p(
            q_ref, k_ref, lse_ref, sm_scale, causal, qi, ki,
            block_q, block_k, valid_len, vl_ref, use_vl,
        )  # (bq, bk)
        do = do_ref[0].astype(jnp.float32)  # (bq, d)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, d)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (bq, bk)
        ds = p * (dp - _delta(o_ref, do_ref))
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (bk, d)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, sm_scale, block_q, block_k, interpret, valid_len, use_vl,
         res, do):
    q, k, v, vl, o, lse = res
    bh, seq, d = q.shape
    block_q = _blk(seq, block_q, "flash bwd q")
    block_k = _blk(seq, block_k, "flash bwd k")
    num_q, num_kv = seq // block_q, seq // block_k
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    q_spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec_q = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    lse_spec_q = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_kv=num_kv,
            valid_len=valid_len, use_vl=use_vl,
        ),
        grid=(bh, num_q, num_kv),
        in_specs=[smem, q_spec_q, k_spec_q, k_spec_q, q_spec_q, q_spec_q,
                  lse_spec_q],
        out_specs=q_spec_q,
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        name="flash_bwd_dq",
        interpret=interpret,
    )(vl, q, k, v, o, do, lse)

    # dK/dV: kv blocks outer, q blocks inner.
    q_spec_k = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    k_spec_k = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    lse_spec_k = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=num_q,
            valid_len=valid_len, use_vl=use_vl,
        ),
        grid=(bh, num_kv, num_q),
        in_specs=[smem, q_spec_k, k_spec_k, k_spec_k, q_spec_k, q_spec_k,
                  lse_spec_k],
        out_specs=[k_spec_k, k_spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(vl, q, k, v, o, do, lse)
    # vl is an integer input: no cotangent.
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, vl, causal, sm_scale, block_q, block_k, interpret,
           valid_len=None, use_vl=False):
    o, _ = _fwd(q, k, v, vl, causal, sm_scale, block_q, block_k, interpret,
                valid_len, use_vl)
    return o


def _flash_fwd(q, k, v, vl, causal, sm_scale, block_q, block_k, interpret,
               valid_len, use_vl):
    o, lse = _fwd(q, k, v, vl, causal, sm_scale, block_q, block_k, interpret,
                  valid_len, use_vl)
    return o, (q, k, v, vl, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def flash_attention(
    q, k, v, *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    mesh=None,
    head_axes: tuple[str, ...] = ("tp",),
    kv_valid_lens=None,
):
    """Fused attention over ``[batch, seq, heads, head_dim]`` inputs.

    Matches ``softmax(scale * Q K^T [+ causal mask]) V`` with fp32 softmax,
    differentiable via the flash backward kernels. ``interpret=None`` auto-
    selects interpret mode off-TPU (CPU test harness).

    Sharding: a ``pallas_call`` is an opaque custom call the SPMD partitioner
    would replicate around, so under a mesh (passed explicitly or ambient via
    ``sharding.activation_mesh`` — the Trainer's steps install one) the kernel
    runs inside ``shard_map`` over batch ('dp','fsdp') and heads
    (``head_axes``, default ('tp',); Ulysses passes ('tp','cp') for its
    seq-gathered/head-sharded interior layout) — attention is independent per
    (batch, head), so each shard's kernel is the whole computation for its
    slice. Sequence stays unsharded inside the kernel (ring attention covers
    seq-sharded execution).

    ``kv_valid_lens`` ([batch] int32): per-sequence key-padding limit —
    columns at or beyond a sequence's valid length never contribute
    (equivalent to a CONTIGUOUS-PREFIX key mask, the padded-batch case; the
    caller is responsible for that contiguity). Query rows at padded
    positions produce garbage the loss must mask, as with any
    padding-masked attention.
    """
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    b, s, h, d = q.shape
    if sm_scale is None:
        sm_scale = float(1.0 / np.sqrt(d))
    if interpret is None:
        interpret = _default_interpret()
    use_vl = kv_valid_lens is not None
    if use_vl:
        kv_valid_lens = jnp.asarray(kv_valid_lens, jnp.int32)
        if kv_valid_lens.shape != (b,):
            raise ValueError(
                f"kv_valid_lens must be [batch]={b}, got "
                f"{kv_valid_lens.shape}"
            )
    else:
        kv_valid_lens = jnp.full((b,), s, jnp.int32)

    def local(q, k, v, vls):
        lb, ls, lh, ld = q.shape
        # Non-block-multiple sequences (ViT's 197 tokens, BERT's 509, ...)
        # are right-padded to the block grid; padded kv columns are masked
        # inside the kernels via the static valid_len, padded q rows sliced
        # off here. No dynamic shapes reach Mosaic. The effective block
        # sizes chosen here are passed INTO the kernels (recomputing them
        # from the padded length would disagree with the pad).
        bq, bk = min(block_q, ls), min(block_k, ls)
        if ls % bq == 0 and ls % bk == 0:
            ls_p, valid = ls, None
        else:
            # One common block keeps the pad bounded at < block (the lcm of
            # unequal blocks can blow the pad up to bq*bk).
            bq = bk = min(bq, bk)
            ls_p = ((ls + bq - 1) // bq) * bq
            valid = ls
            pad = lambda t: jnp.pad(t, ((0, 0), (0, ls_p - ls), (0, 0), (0, 0)))  # noqa: E731
            q, k, v = pad(q), pad(k), pad(v)
        to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(lb * lh, ls_p, ld)  # noqa: E731
        # One limit per folded (batch, head) row, b-major like the fold.
        vl_bh = jnp.repeat(vls, lh)
        o = _flash(
            to_bhsd(q), to_bhsd(k), to_bhsd(v), vl_bh,
            causal, sm_scale, bq, bk, interpret, valid, use_vl,
        )
        o = o.reshape(lb, lh, ls_p, ld).transpose(0, 2, 1, 3)
        return o[:, :ls] if valid is not None else o

    if mesh is None:
        from ..sharding import _MESH_CTX

        mesh = _MESH_CTX.get()
    if mesh is not None:
        batch_ways = math.prod(mesh.shape[a] for a in BATCH_AXES)
        head_ways = math.prod(mesh.shape[a] for a in head_axes)
        if batch_ways * head_ways > 1:
            if b % batch_ways:
                raise ValueError(
                    f"flash: batch={b} not divisible by dp*fsdp={batch_ways}"
                )
            if h % head_ways:
                raise ValueError(
                    f"flash: heads={h} not divisible by "
                    f"{'*'.join(head_axes)}={head_ways}"
                )
            spec = P(BATCH_AXES, None, head_axes, None)
            vl_spec = P(BATCH_AXES)
            # check_vma=False: same jax-0.9.0 pallas-in-shard_map typing
            # limitation as ring_attention_pallas.py — no collectives exist
            # in the body, each shard is independent.
            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(spec, spec, spec, vl_spec), out_specs=spec,
                check_vma=False,
            )(q, k, v, kv_valid_lens)
    return local(q, k, v, kv_valid_lens)


def attention_reference(q, k, v, *, causal: bool = False, sm_scale=None):
    """Pure-jnp oracle (same math, materialized scores) for tests."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        ql, kl = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )
