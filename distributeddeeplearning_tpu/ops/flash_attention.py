"""Fused flash attention — Pallas Mosaic kernels for the TPU MXU.

This is the TPU-native equivalent of the reference's fused "CUDA
forward/backward kernels" for attention (``BASELINE.json:5``): one kernel
computes the whole softmax(QK^T)V tile-by-tile in VMEM with the
online-softmax recurrence, so the [seq, seq] score matrix never
materializes in HBM. The backward pass is the standard two-kernel
recomputation scheme (dQ by query blocks, dK/dV by key blocks) wired up
as a ``jax.custom_vjp``.

The tile rule (``tiles``; measured in PERF.md §6, PR 27). A call that names
no block gets 512 x 512 tiles (512 x 1024 without a causal diagonal), cut to
the sequence padded to a lane width; ``block_q``/``block_k`` override it. The
grid is ``(batch*heads, blocks of the kernel's own operand, major blocks of
the swept one)``: the forward and dQ kernels own a q block and sweep K and V,
the dK/dV kernel owns a kv block and sweeps Q and dO. The swept pair stays in
VMEM whole (up to ``_RESIDENT_BYTES``: 4096 rows at head size 64; one major
block, so the third grid axis has one step) and the sweep over its
``block``-row tiles is a loop INSIDE the kernel, because a tile's fixed cost
is the same as a grid step or as a loop iteration and only its size
amortises it. At the training cell's ``bf16[8, 1024, 16, 64]`` that is 256
grid steps a call where the 128 x 128 grid had 8,192.

Layout notes (see pallas_guide.md):
- causal: the loop's bounds stop at the diagonal (forward, dQ) or start at
  it (dK/dV), so tiles above it are left out, not stepped over; the tiles
  the diagonal crosses run in a second loop that builds the
  ``broadcasted_iota`` mask, the others build none. Where the sequence takes
  several major blocks the swept pair's index map clamps to the diagonal, so
  a step with nothing to do fetches nothing;
- the matmul operands go to the MXU in the inputs' own dtype (bfloat16 in
  training, float32 in the parity tests) with
  ``preferred_element_type=float32``; ``sm_scale`` multiplies the float32
  scores; m, l, lse, delta, the accumulators and the softmax arithmetic are
  float32; p and ds are cast to the input dtype only as operands of the
  second products (what the ``xla`` core does with ``probs.astype(dtype)``);
- the forward keeps m lane-broadcast ``(block_q, 128)`` and l as 128 partial
  sums a row: adding a tile's lane chunks is elementwise, the one reduction
  across lanes waits for the last tile;
- the dK/dV kernel works on transposed tiles (keys down the sublanes,
  queries along the lanes), so no product transposes anything and lse and
  delta broadcast as rows; ``delta = sum(dO * O)`` is computed once a q block
  by the dQ kernel and handed over like lse;
- lse and delta live in HBM as rows ``[bh, seq/block_q, 1, block_q]``: a
  ``[bh, seq, 1]`` column is stored 128 lanes wide there (64 MB a layer where
  0.5 MB is meant, held from the forward to the backward);
- on CPU backends the kernels run in interpret mode, which is how the unit
  tests exercise them without a TPU.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

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
# the tile rule
# ---------------------------------------------------------------------------

# From the block sweep on a v5e at bf16[8, 1024, 16, 64] (PERF.md §6, PR 27).
# What a tile costs beside its score arithmetic is paid once a q row a tile
# (the running max's reduction across lanes, the rescale of l and acc), so
# small tiles lose whether they are grid steps or loop iterations: 128 x 128
# took 10.1 ms a layer, 512 x 512 takes 1.9. Past 512 a causal sweep wastes
# more above the diagonal than it saves (1024 x 1024: 2.1 ms); a sweep with
# no diagonal takes the longer k block (512 x 1024: 2.0 ms against 2.4).
_BLOCK = 512
_BLOCK_K_NO_DIAGONAL = 1024
# K and V (dK/dV: Q and dO) stay in VMEM whole up to this many bytes, lane
# padding and double buffering counted, so that the sweep over them is a
# loop inside the kernel and not a grid axis: 4096 rows at head size 64.
_RESIDENT_BYTES = 4 << 20


class Tiles(NamedTuple):
    """How one call is cut. ``seq`` is the padded length; ``major_k`` rows of
    K and V (``major_q`` of Q and dO) are in VMEM at a time, swept in
    ``block_k`` (``block_q``) steps by a loop inside the kernel."""

    block_q: int
    block_k: int
    seq: int
    major_q: int
    major_k: int


def tiles(seq: int, head_dim: int, dtype, causal: bool,
          block_q: int | None = None, block_k: int | None = None) -> Tiles:
    """The tiling of a call from what it can see.

    With no block named, the sequence is padded to the lane width (below it,
    to the bfloat16 sublane pack) and each block is the largest multiple of
    that unit which divides the padded sequence and does not pass its target
    (``_BLOCK``; ``_BLOCK_K_NO_DIAGONAL`` for the k block of a non-causal
    call). A named block is cut to the sequence; where it does not divide the
    sequence both blocks become the smaller one, which keeps the pad under a
    block (the lcm of unequal blocks can blow it up to bq*bk).
    """
    if block_q is None and block_k is None:
        unit = _LANES if seq >= _LANES else 16
        seq_p = -(-seq // unit) * unit

        def fit(target):
            return max(
                b for b in range(unit, min(target, seq_p) + 1, unit)
                if seq_p % b == 0
            )

        bq = fit(_BLOCK)
        bk = fit(_BLOCK if causal else _BLOCK_K_NO_DIAGONAL)
    else:
        bq, bk = min(block_q or block_k, seq), min(block_k or block_q, seq)
        if seq % bq or seq % bk:
            bq = bk = min(bq, bk)
        seq_p = -(-seq // bq) * bq

    def major(block):
        # Two operands, double-buffered, each row padded to a lane width.
        row = 2 * 2 * max(head_dim, _LANES) * jnp.dtype(dtype).itemsize
        n = seq_p // block
        return block * max(
            t for t in range(1, n + 1)
            if n % t == 0 and (t == 1 or block * t * row <= _RESIDENT_BYTES)
        )

    return Tiles(bq, bk, seq_p, major(bq), major(bk))


# ---------------------------------------------------------------------------
# what the three kernels share
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _scores(a, b, sm_scale):
    """float32 ``sm_scale * a @ b.T``: the operands go to the MXU in their
    own dtype (bfloat16 in training, float32 in the parity tests) and the
    scale is applied to the float32 product."""
    return sm_scale * jax.lax.dot_general(
        a, b, _NT, preferred_element_type=jnp.float32
    )


def _loop(lo, hi, tile):
    jax.lax.fori_loop(lo, hi, lambda t, c: (tile(t), c)[1], None)


def _mask(s, q0, k0, q_axis, *, diagonal, valid_len, vl):
    """``_NEG_INF`` where a key may not be seen. ``q0``/``k0``: the tile's
    first global positions; ``q_axis``: the axis of ``s`` that runs over
    queries. ``diagonal``: the causal diagonal crosses this tile, so keys
    after their query go. ``valid_len`` (static, or None): the sequence was
    right-padded to a block multiple and padded kv columns must not
    contribute. ``vl`` (runtime, from SMEM, or None): this (batch, head)'s
    key-padding limit, which any tile may cross."""
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    keep = None
    if diagonal:
        keep = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) >= kpos
    for limit in (valid_len, vl):
        if limit is not None:
            below = kpos < limit
            keep = below if keep is None else keep & below
    return s if keep is None else jnp.where(keep, s, _NEG_INF)


def _sweep_kv(tile, qi, first, nsub, *, block_q, block_k, num_kv, causal,
              valid_len):
    """Run ``tile(j, masked)`` over the ``nsub`` kv sub-blocks from global
    sub-block ``first`` on: those q block ``qi`` sees whole unmasked, those
    it sees through a mask masked; the rest lie above the diagonal and are
    not visited."""
    if causal:
        n_full = (qi * block_q) // block_k
        n_vis = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        n_full = num_kv if valid_len is None else valid_len // block_k
        n_vis = num_kv
    a, b = jnp.clip(n_full - first, 0, nsub), jnp.clip(n_vis - first, 0, nsub)
    _loop(0, a, lambda j: tile(j, False))
    _loop(a, b, lambda j: tile(j, True))


def _as_row(col):
    """(rows, 128) lane-broadcast per-row values -> (1, rows): how lse and
    delta are stored (the module docstring says why)."""
    return col.T[:1, :]


def _as_col(row):
    """(1, rows) -> (rows, 128) lane-broadcast: ``_as_row`` undone."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _row_shape(bh, seq, block_q):
    return jax.ShapeDtypeStruct(
        (bh, seq // block_q, 1, block_q), jnp.float32
    )


def _row_spec(block_q):
    """One q block's row over grid (bh, q block, major)."""
    return pl.BlockSpec((1, 1, 1, block_q), lambda b, i, g: (b, i, 0, 0))


def _kv_major_map(causal, block_q, major):
    """Index map of a K/V major block over grid (bh, q block, major). Under
    causal a major block above the diagonal is never read, so the map stays
    on the last one that is and the step fetches nothing."""
    if not causal:
        return lambda b, i, g: (b, g, 0)
    return lambda b, i, g: (
        b, jnp.minimum(g, ((i + 1) * block_q - 1) // major), 0
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, causal, block_q, block_k, num_major, valid_len=None,
    use_vl=False,
):
    qi = pl.program_id(1)
    vl = vl_ref[pl.program_id(0)] if use_vl else None
    g = pl.program_id(2)
    nsub = k_ref.shape[1] // block_k

    @pl.when(g == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    lanes = l_scr.shape[1]

    def tile(j, masked):
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        s = _scores(q_ref[0], k_ref[0, ks, :], sm_scale)  # (bq, bk)
        s = _mask(
            s, qi * block_q, (g * nsub + j) * block_k, 0,
            diagonal=causal and masked, vl=vl,
            # Under causal the diagonal already hides the pad from valid rows.
            valid_len=None if causal or not masked else valid_len,
        )
        m_prev = m_scr[:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        # l is kept as partial sums, one a lane: adding the tile's lane
        # chunks is elementwise, and the one reduction across lanes waits
        # for _finalize.
        l_scr[:] = l_scr[:] * alpha + sum(
            p[:, c:c + lanes] for c in range(0, block_k, lanes)
        )
        v = v_ref[0, ks, :]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    _sweep_kv(
        tile, qi, g * nsub, nsub, block_q=block_q, block_k=block_k,
        num_kv=num_major * nsub, causal=causal, valid_len=valid_len,
    )

    @pl.when(g == num_major - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_scr[:], axis=1, keepdims=True), 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _as_row(m_scr[:] + jnp.log(l))


# jit here and on _bwd: a model calls flash_attention once a layer with the
# same shapes, and the jit's cache hands every call after the first the same
# jaxpr, so each kernel is traced, and lowered to Mosaic, once a program and
# not once a layer (24 layers: 2.2 s of every start, cached or not).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _fwd(q, k, v, vl, causal, sm_scale, t: Tiles, interpret,
         valid_len=None, use_vl=False):
    """q/k/v: [bh, seq, d]; vl: [bh] int32 per-row kv limits (used when
    ``use_vl``) -> (o [bh, seq, d], lse [bh, seq/block_q, 1, block_q] fp32:
    a row a q block, see ``_as_row``)."""
    bh, seq, d = q.shape
    block_q, block_k, major = t.block_q, t.block_k, t.major_k
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_major=seq // major,
        valid_len=valid_len, use_vl=use_vl,
    )
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, g: (b, i, 0))
    kv_spec = pl.BlockSpec((1, major, d), _kv_major_map(causal, block_q, major))
    return pl.pallas_call(
        kernel,
        grid=(bh, seq // block_q, seq // major),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, kv_spec,
                  kv_spec],
        out_specs=[q_spec, _row_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            _row_shape(bh, seq, block_q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM(
                (block_q, block_k if block_k % _LANES else _LANES),
                jnp.float32,
            ),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="flash_fwd",
        interpret=interpret,
    )(vl, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, delta_ref,
    dq_scr, lse_scr, delta_scr,
    *, sm_scale, causal, block_q, block_k, num_major, valid_len=None,
    use_vl=False,
):
    qi = pl.program_id(1)
    vl = vl_ref[pl.program_id(0)] if use_vl else None
    g = pl.program_id(2)
    nsub = k_ref.shape[1] // block_k

    @pl.when(g == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta_i = sum_d dO_id O_id depends only on the q block: computed
        # here once, and handed on to the dK/dV kernel as a row like lse.
        delta_scr[:] = jnp.broadcast_to(
            jnp.sum(
                do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                axis=-1, keepdims=True,
            ),
            delta_scr.shape,
        )
        delta_ref[0, 0] = _as_row(delta_scr[:])
        lse_scr[:] = _as_col(lse_ref[0, 0])

    def tile(j, masked):
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, ks, :]
        s = _scores(q_ref[0], k, sm_scale)  # (bq, bk)
        s = _mask(
            s, qi * block_q, (g * nsub + j) * block_k, 0,
            diagonal=causal and masked, vl=vl,
            # Under causal the diagonal already hides the pad from valid rows.
            valid_len=None if causal or not masked else valid_len,
        )
        p = jnp.exp(s - lse_scr[:, :1])  # masked -> 0
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0, ks, :], _NT,
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        ds = p * (dp - delta_scr[:, :1])
        dq_scr[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    _sweep_kv(
        tile, qi, g * nsub, nsub, block_q=block_q, block_k=block_k,
        num_kv=num_major * nsub, causal=causal, valid_len=valid_len,
    )

    @pl.when(g == num_major - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(
    vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, causal, block_q, block_k, num_major, valid_len=None,
    use_vl=False,
):
    """One kv block against the q blocks that see it, on TRANSPOSED tiles
    (keys down the sublanes, queries along the lanes): every product is then
    ``a @ b`` or ``a @ b.T`` with nothing to transpose, and lse and delta
    come as rows."""
    kj = pl.program_id(1)
    vl = vl_ref[pl.program_id(0)] if use_vl else None
    g = pl.program_id(2)
    nsub = q_ref.shape[1] // block_q

    @pl.when(g == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(i, masked):
        qs = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q, do = q_ref[0, qs, :], do_ref[0, qs, :]
        st = _scores(k_ref[0], q, sm_scale)  # (bk, bq)
        st = _mask(
            st, (g * nsub + i) * block_q, kj * block_k, 1,
            diagonal=causal and masked, vl=vl,
            valid_len=None if causal else valid_len,
        )
        pt = jnp.exp(st - lse_ref[0, i])  # lse row is (1, bq); masked -> 0
        dv_scr[:] += jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32
        )  # (bk, d)
        dpt = jax.lax.dot_general(
            v_ref[0], do, _NT, preferred_element_type=jnp.float32
        )  # (bk, bq)
        dst = pt * (dpt - delta_ref[0, i])
        dk_scr[:] += jnp.dot(
            dst.astype(q.dtype), q, preferred_element_type=jnp.float32
        )  # (bk, d)

    if causal:
        # q sub-blocks before the diagonal see nothing of this kv block; the
        # first that do see it through the mask, the rest whole.
        first = g * nsub
        a = jnp.clip((kj * block_k) // block_q - first, 0, nsub)
        b = jnp.clip(
            ((kj + 1) * block_k + block_q - 1) // block_q - first, 0, nsub
        )
        _loop(a, b, lambda i: tile(i, True))
        _loop(b, nsub, lambda i: tile(i, False))
    else:
        _loop(0, nsub, lambda i: tile(i, True))

    @pl.when(g == num_major - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _bwd(causal, sm_scale, t: Tiles, interpret, valid_len, use_vl, res, do):
    q, k, v, vl, o, lse = res
    bh, seq, d = q.shape
    block_q, block_k = t.block_q, t.block_k
    num_q, num_kv = seq // block_q, seq // block_k
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    static = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
        valid_len=valid_len, use_vl=use_vl,
    )

    # dQ (and delta): q blocks on the grid, the kv sweep inside.
    major = t.major_k
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, g: (b, i, 0))
    kv_spec = pl.BlockSpec((1, major, d), _kv_major_map(causal, block_q, major))
    row = _row_spec(block_q)
    dq, delta = pl.pallas_call(
        functools.partial(_dq_kernel, num_major=seq // major, **static),
        grid=(bh, num_q, seq // major),
        in_specs=[smem, q_spec, kv_spec, kv_spec, q_spec, q_spec, row],
        out_specs=[q_spec, row],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            _row_shape(bh, seq, block_q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="flash_bwd_dq",
        interpret=interpret,
    )(vl, q, k, v, o, do, lse)

    # dK/dV: kv blocks on the grid, the q sweep inside, from the diagonal on.
    major = t.major_q
    nsub = major // block_q

    def q_major(b, j, g):
        # Under causal a q major block wholly before the diagonal is never
        # read: the map waits on the first that is.
        return b, (jnp.maximum(g, (j * block_k) // major) if causal else g)

    qm_spec = pl.BlockSpec((1, major, d), lambda b, j, g: (*q_major(b, j, g), 0))
    row_spec = pl.BlockSpec(
        (1, nsub, 1, block_q), lambda b, j, g: (*q_major(b, j, g), 0, 0)
    )
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, j, g: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_major=seq // major, **static),
        grid=(bh, num_kv, seq // major),
        in_specs=[smem, qm_spec, k_spec, k_spec, qm_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(vl, q, k, v, do, lse, delta)
    # vl is an integer input: no cotangent.
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, vl, causal, sm_scale, t, interpret, valid_len=None,
           use_vl=False):
    o, _ = _fwd(q, k, v, vl, causal, sm_scale, t, interpret, valid_len, use_vl)
    return o


def _flash_fwd(q, k, v, vl, causal, sm_scale, t, interpret, valid_len,
               use_vl):
    o, lse = _fwd(q, k, v, vl, causal, sm_scale, t, interpret, valid_len,
                  use_vl)
    return o, (q, k, v, vl, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def flash_attention(
    q, k, v, *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
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
        # off here. No dynamic shapes reach Mosaic. The tiling chosen here is
        # passed INTO the kernels (recomputing it from the padded length
        # would disagree with the pad).
        cut = tiles(ls, ld, q.dtype, causal, block_q, block_k)
        ls_p, valid = cut.seq, (ls if cut.seq != ls else None)
        if valid is not None:
            pad = lambda t: jnp.pad(t, ((0, 0), (0, ls_p - ls), (0, 0), (0, 0)))  # noqa: E731
            q, k, v = pad(q), pad(k), pad(v)
        to_bhsd = lambda t: t.transpose(0, 2, 1, 3).reshape(lb * lh, ls_p, ld)  # noqa: E731
        # One limit per folded (batch, head) row, b-major like the fold.
        vl_bh = jnp.repeat(vls, lh)
        o = _flash(
            to_bhsd(q), to_bhsd(k), to_bhsd(v), vl_bh,
            causal, sm_scale, cut, interpret, valid, use_vl,
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
