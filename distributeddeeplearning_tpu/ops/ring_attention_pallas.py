"""Fused Pallas ring attention — the second of the two mandated ring
implementations (SURVEY.md §5 long-context: "implemented twice: a
pure-shard_map reference AND a Pallas v5e kernel").

Division of labor, chosen for the TPU execution model:

- The RING stays at the JAX level: ``shard_map`` + ``lax.ppermute`` rotate
  the KV block one ICI neighbor per step, exactly as in the reference
  implementation (``ring_attention.py``). Collectives emitted by XLA are
  asynchronous; the latency-hiding scheduler overlaps the ppermute of step
  t+1's block with the kernel of step t — in-kernel RDMA would buy nothing
  on this axis and would forfeit XLA's scheduling.
- The per-visit BLOCK ATTENTION is the fused Pallas kernel: a flash-style
  blockwise pass over the visiting KV block that consumes and produces the
  online-softmax carries (m, l, acc), so the [seq_local, seq_local] score
  tile lives only in VMEM. This is the flash-attention forward kernel
  (``flash_attention.py``) generalized to EXTERNAL carries: the softmax
  state survives across ring steps instead of across one kernel's grid.

Causality: device i's queries own global positions [i*Lq, (i+1)*Lq); at ring
step t the visiting block is (i+t) mod cp. Fully-hidden blocks (src > i) are
skipped at the JAX level with ``lax.cond`` (no kernel launch, no MXU work);
the diagonal block applies the local causal mask inside the kernel (mode
scalar in SMEM, since the visiting block id is a traced value).

Backward: fused as well. The forward saves the per-row LSE; the backward
makes one more lap of the ring rotating ``(k, v, dk, dv)`` together — each
visit recomputes the visiting block's probabilities from (q, k, lse) and
runs TWO kernels (flash-style): a dq kernel (kv-innermost grid, dq carried
in VMEM scratch) and a dk/dv kernel (q-innermost grid, accumulators seeded
from the rotating dk/dv and flushed back into them). After a full lap the
accumulators arrive back at their home device. Hidden blocks skip both
kernels at the JAX level (``lax.cond``), exactly like the forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..mesh import BATCH_AXES
from .flash_attention import _blk, _default_interpret

_NEG_INF = -1e30
_LANES = 128


def _ring_step_kernel(
    mode_ref,  # SMEM (1,1) int32: 1 = diagonal block (local causal mask)
    q_ref, k_ref, v_ref, m_in, l_in, acc_in,
    m_out, l_out, acc_out,
    m_scr, l_scr, acc_scr,
    *, block_q, block_k, num_kv,
):
    """One visiting KV block folded into the online-softmax carries.

    Grid: (batch*heads, q_blocks, kv_blocks); kv is the sequential innermost
    dim, carries live in VMEM scratch across it, seeded from the inputs at
    ki==0 and flushed to the outputs at ki==num_kv-1. q is pre-scaled.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _load_carries():
        m_scr[:] = jnp.broadcast_to(m_in[0], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_in[0], l_scr.shape)
        acc_scr[:] = acc_in[0]

    s = jax.lax.dot_general(
        q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (bq, bk)
    row = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    # mode 0 (fully visible block): keep every score. mode 1 (diagonal):
    # local causal mask. Hidden blocks never reach this kernel.
    s = jnp.where((mode_ref[0, 0] == 0) | (row >= col), s, _NEG_INF)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv - 1)
    def _flush_carries():
        m_out[0] = m_scr[:, :1]
        l_out[0] = l_scr[:, :1]
        acc_out[0] = acc_scr[:]


def _ring_step(qf, kt, vt, m, l, acc, mode, *, block_q, block_k, interpret):
    """qf (pre-scaled fp32) [bh, lq, d]; kt/vt [bh, lk, d]; carries
    m/l [bh, lq, 1], acc [bh, lq, d] -> updated carries."""
    bh, lq, d = qf.shape
    lk = kt.shape[1]
    bq = _blk(lq, block_q, "ring q")
    bk = _blk(lk, block_k, "ring k")
    num_q, num_kv = lq // bq, lk // bk
    kernel = functools.partial(
        _ring_step_kernel, block_q=bq, block_k=bk, num_kv=num_kv,
    )
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    c_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # mode scalar
            q_spec, k_spec, k_spec, c_spec, c_spec, q_spec,
        ],
        out_specs=[c_spec, c_spec, q_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        name="ring_fwd",
        interpret=interpret,
    )(mode, qf, kt, vt, m, l, acc)


def _ring_local_pallas_fwd_impl(
    q, k, v, *, axis_name, causal, block_q, block_k, interpret
):
    """Per-device forward (inside shard_map): scan ring steps, each step one
    fused kernel launch + one KV rotation."""
    cp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, lq, d)  # noqa: E731
    qf = fold(q).astype(jnp.float32) * scale
    kf, vf = fold(k), fold(v)

    m0 = jnp.full((b * h, lq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b * h, lq, 1), jnp.float32)
    acc0 = jnp.zeros((b * h, lq, d), jnp.float32)

    def update(m, l, acc, kt, vt, t):
        src = (idx + t) % cp
        mode = jnp.where(src == idx, jnp.int32(1), jnp.int32(0)).reshape(1, 1)
        step = functools.partial(
            _ring_step, block_q=block_q, block_k=block_k, interpret=interpret,
        )
        if not causal:
            return step(qf, kt, vt, m, l, acc, jnp.zeros((1, 1), jnp.int32))
        # Hidden blocks (src > idx): no kernel launch at all.
        return jax.lax.cond(
            src <= idx,
            lambda args: step(*args),
            lambda args: (args[3], args[4], args[5]),
            (qf, kt, vt, m, l, acc, mode),
        )

    def scan_step(carry, t):
        m, l, acc, kt, vt = carry
        m, l, acc = update(m, l, acc, kt, vt, t)
        perm = [(i, (i - 1) % cp) for i in range(cp)]
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return (m, l, acc, kt, vt), None

    # Mirror the reference: scan cp-1 rotations, peel the final block so the
    # last (unconsumed) ppermute is never emitted.
    (m, l, acc, kt, vt), _ = jax.lax.scan(
        scan_step, (m0, l0, acc0, kf, vf), jnp.arange(cp - 1)
    )
    m, l, acc = update(m, l, acc, kt, vt, cp - 1)

    out = acc / jnp.maximum(l, 1e-30)  # [bh, lq, d]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [bh, lq, 1]
    return (
        out.reshape(b, h, lq, d).transpose(0, 2, 1, 3).astype(q.dtype),
        lse,
    )


# ---------------------------------------------------------------------------
# fused backward: one more ring lap rotating (k, v, dk, dv)
# ---------------------------------------------------------------------------


def _ring_recompute_p_ds(
    mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    qi, ki, bq, bk, sm_scale,
):
    """(p, ds, do) for one (q-block, kv-block) tile — the shared
    probability/score-cotangent recompute both backward kernels consume
    (mode-scalar analogue of ``flash_attention._recompute_p``; keeping it in
    one place keeps dq and dk/dv bit-consistent)."""
    q = q_ref[0].astype(jnp.float32) * sm_scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where((mode_ref[0, 0] == 0) | (row >= col), s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0])  # (bq, bk)
    do = do_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(
        do, v_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0])
    return p, ds, do


def _ring_dq_kernel(
    mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_in,
    dq_out, dq_scr,
    *, sm_scale, block_q, block_k, num_kv,
):
    """dq contribution of ONE visiting KV block, accumulated onto the carried
    dq. Grid (bh, q_blocks, kv_blocks); kv innermost, dq in VMEM scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _seed():
        dq_scr[:] = dq_in[0]

    _, ds, _ = _ring_recompute_p_ds(
        mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        qi, ki, block_q, block_k, sm_scale,
    )
    dq_scr[:] += sm_scale * jnp.dot(
        ds, k_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )

    @pl.when(ki == num_kv - 1)
    def _flush():
        dq_out[0] = dq_scr[:]


def _ring_dkv_kernel(
    mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_in, dv_in, dk_out, dv_out, dk_scr, dv_scr,
    *, sm_scale, block_q, block_k, num_q,
):
    """dk/dv contribution of this device's queries to the visiting block,
    accumulated onto the ROTATING dk/dv. Grid (bh, kv_blocks, q_blocks)."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _seed():
        dk_scr[:] = dk_in[0]
        dv_scr[:] = dv_in[0]

    p, ds, do = _ring_recompute_p_ds(
        mode_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        qi, ki, block_q, block_k, sm_scale,
    )
    dv_scr[:] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dk_scr[:] += sm_scale * jax.lax.dot_general(
        ds, q_ref[0].astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(qi == num_q - 1)
    def _flush():
        dk_out[0] = dk_scr[:]
        dv_out[0] = dv_scr[:]


def _ring_bwd_step(
    q, kt, vt, do, lse, delta, dq, dkt, dvt, mode,
    *, sm_scale, block_q, block_k, interpret,
):
    """One visiting block folded into (dq, dk_t, dv_t). All [bh, l, d] (q-
    or k-sided); lse/delta [bh, lq, 1]."""
    bh, lq, d = q.shape
    lk = kt.shape[1]
    bq = _blk(lq, block_q, "ring bwd q")
    bk = _blk(lk, block_k, "ring bwd k")
    num_q, num_kv = lq // bq, lk // bk
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    c_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dq = pl.pallas_call(
        functools.partial(
            _ring_dq_kernel, sm_scale=sm_scale,
            block_q=bq, block_k=bk, num_kv=num_kv,
        ),
        grid=(bh, num_q, num_kv),
        in_specs=[smem, q_spec, k_spec, k_spec, q_spec, c_spec, c_spec,
                  q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="ring_bwd_dq",
        interpret=interpret,
    )(mode, q, kt, vt, do, lse, delta, dq)

    # kv-sided views of the q-sided blocks.
    q_spec_k = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    k_spec_k = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    c_spec_k = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, j, 0))
    dkt, dvt = pl.pallas_call(
        functools.partial(
            _ring_dkv_kernel, sm_scale=sm_scale,
            block_q=bq, block_k=bk, num_q=num_q,
        ),
        grid=(bh, num_kv, num_q),
        in_specs=[smem, q_spec_k, k_spec_k, k_spec_k, q_spec_k, c_spec_k,
                  c_spec_k, k_spec_k, k_spec_k],
        out_specs=[k_spec_k, k_spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, lk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="ring_bwd_dkv",
        interpret=interpret,
    )(mode, q, kt, vt, do, lse, delta, dkt, dvt)
    return dq, dkt, dvt


def _ring_local_pallas_bwd_impl(
    q, k, v, out, lse, g, *, axis_name, causal, block_q, block_k, interpret
):
    cp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, lq, d)  # noqa: E731
    qf, kf, vf = fold(q), fold(k), fold(v)
    do = fold(g).astype(jnp.float32)
    of = fold(out).astype(jnp.float32)
    delta = jnp.sum(do * of, axis=-1, keepdims=True)  # [bh, lq, 1]

    dq0 = jnp.zeros_like(qf, jnp.float32)
    dk0 = jnp.zeros_like(kf, jnp.float32)
    dv0 = jnp.zeros_like(vf, jnp.float32)

    def update(dq, kt, vt, dkt, dvt, t):
        src = (idx + t) % cp
        mode = jnp.where(src == idx, jnp.int32(1), jnp.int32(0)).reshape(1, 1)
        step = functools.partial(
            _ring_bwd_step, sm_scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        if not causal:
            return step(
                qf, kt, vt, do, lse, delta, dq, dkt, dvt,
                jnp.zeros((1, 1), jnp.int32),
            )
        return jax.lax.cond(
            src <= idx,
            lambda args: step(*args),
            lambda args: (args[6], args[7], args[8]),
            (qf, kt, vt, do, lse, delta, dq, dkt, dvt, mode),
        )

    perm = [(i, (i - 1) % cp) for i in range(cp)]

    def scan_step(carry, t):
        dq, kt, vt, dkt, dvt = carry
        dq, dkt, dvt = update(dq, kt, vt, dkt, dvt, t)
        # Rotate KV *and its gradient accumulators* together.
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        dkt = jax.lax.ppermute(dkt, axis_name, perm)
        dvt = jax.lax.ppermute(dvt, axis_name, perm)
        return (dq, kt, vt, dkt, dvt), None

    # Peel the final step (mirroring the forward): after it, only the
    # ACCUMULATORS need one last hop home — the kt/vt ppermutes of a full
    # cp-lap would be dead comms.
    (dq, kt, vt, dk, dv), _ = jax.lax.scan(
        scan_step, (dq0, kf, vf, dk0, dv0), jnp.arange(cp - 1)
    )
    dq, dk, dv = update(dq, kt, vt, dk, dv, cp - 1)
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    unfold = lambda t, dt: (  # noqa: E731
        t.reshape(b, h, lq, d).transpose(0, 2, 1, 3).astype(dt)
    )
    return unfold(dq, q.dtype), unfold(dk, k.dtype), unfold(dv, v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_local_pallas(q, k, v, axis_name, causal, block_q, block_k, interpret):
    out, _ = _ring_local_pallas_fwd_impl(
        q, k, v, axis_name=axis_name, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _ring_local_pallas_fwd(
    q, k, v, axis_name, causal, block_q, block_k, interpret
):
    out, lse = _ring_local_pallas_fwd_impl(
        q, k, v, axis_name=axis_name, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _ring_local_pallas_bwd(
    axis_name, causal, block_q, block_k, interpret, res, g
):
    q, k, v, out, lse = res
    return _ring_local_pallas_bwd_impl(
        q, k, v, out, lse, g,
        axis_name=axis_name, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


_ring_local_pallas.defvjp(_ring_local_pallas_fwd, _ring_local_pallas_bwd)


def ring_attention_pallas(
    q, k, v, mesh: Mesh, *,
    causal: bool = True,
    axis_name: str = "cp",
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Fused-kernel ring attention over ``[batch, seq, heads, head_dim]``
    global arrays — drop-in for :func:`ring_attention.ring_attention`
    (same sharding contract: batch over BATCH_AXES, seq over ``axis_name``,
    heads over 'tp')."""
    from ..parallel.sp_ring import check_ring_shapes

    check_ring_shapes(q.shape[1], mesh.shape[axis_name])
    if q.shape[2] % mesh.shape["tp"]:
        raise ValueError(
            f"ring: heads={q.shape[2]} not divisible by tp={mesh.shape['tp']}"
        )
    if interpret is None:
        interpret = _default_interpret()
    spec = P(BATCH_AXES, axis_name, "tp", None)
    # check_vma=False: jax 0.9.0's varying-manual-axes checker cannot type a
    # pallas_call inside shard_map (its out ShapeDtypeStructs carry vma=None
    # and the check raises at trace time for every call). Collective
    # correctness is unaffected — the ring's ppermutes are explicit — and
    # parity vs the shard_map oracle is asserted in
    # tests/test_context_parallel.py.
    fn = jax.shard_map(
        lambda q, k, v: _ring_local_pallas(
            q, k, v, axis_name, causal, block_q, block_k, interpret
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
