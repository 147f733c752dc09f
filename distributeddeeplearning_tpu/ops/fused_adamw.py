"""Fused AdamW update — Pallas kernel + optax-compatible wrapper.

TPU-native equivalent of the reference's hand-written "CUDA optimizer
step" (``BASELINE.json:5``): one VPU kernel over the whole parameter
tree. Kernel-sized leaves are grouped by (param dtype, decay group) —
at most two groups per dtype with weight decay on (decayed matrices vs
undecayed norm scales) — and each group is processed in fixed-size
BUCKETS of ``_BUCKET_ROWS`` x 128 elements: all full buckets share one
padded ``(rows, 128)`` shape, so the step still compiles ~one kernel
variant per group (plus at most one tail shape) instead of one per leaf
(dozens of remote Mosaic compiles for GPT-2 otherwise), while peak
scratch is ~7 bucket-sized buffers (~450 MiB) rather than ~7 GROUP-sized
ones — the whole-group concat this replaced held an 11.2 GiB temp
allocation for ViT-L's 325M-param decay group (round-5 buffer-assignment
dump; see _BUCKET_ROWS comment). The trade: the per-step
``concatenate``/slice still costs one extra HBM round trip of the
p/g/m/v buffers around the kernel; storing the moments flat (so no
per-step concat is needed) is the known next step. XLA already fuses the
optax elementwise chain well, so this kernel is an *optional* drop-in
(``make_optimizer("adamw_fused", ...)``) — its value is pinning the
fusion and the fp32 moment arithmetic explicitly, and serving as the
template for further fused update rules.

Leaves smaller than one fp32 tile (8x128) stay on the plain-jnp path — a
kernel's padding overhead per bias vector would cost more than it saves.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_MIN_KERNEL_SIZE = _LANES * _SUBLANES  # below this, plain jnp wins
_MAX_BLOCK_ROWS = 1024  # 1024x128 fp32 = 512 KiB per buffer in VMEM
# Per-bucket cap on the flattened group buffers (rows of 128 lanes;
# 131072 rows = 16.8M elements = 64 MiB fp32). Concatenating a whole
# group at once put ~7 group-sized copies (p/g/m/v in, delta/m/v out) on
# the heap at the kernel — for ViT-L's 325M-param decayed group that was
# an 11.2 GiB temp allocation (XLA buffer-assignment dump, round 5),
# pushing the train step past the v5e's 16 GB at the bench batch.
# Bucketing bounds the scratch at ~7 bucket-sized buffers while keeping
# the one-kernel-variant-per-group compile property (all full buckets
# share one shape; a group adds at most one tail shape).
_BUCKET_ROWS = 131072


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(lr_ref, c1_ref, c2_ref, p_ref, g_ref, m_ref, v_ref,
            dp_ref, nm_ref, nv_ref, *, b1, b2, eps, wd):
    # c1/c2 are the bias corrections 1/(1-b1^t), 1/(1-b2^t), precomputed
    # host-side (Mosaic has no scalar powf).
    lr = lr_ref[0, 0]
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    mhat = m * c1_ref[0, 0]
    vhat = v * c2_ref[0, 0]
    p = p_ref[:].astype(jnp.float32)
    delta = -lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    dp_ref[:] = delta.astype(dp_ref.dtype)
    nm_ref[:] = m
    nv_ref[:] = v


def _pad_2d(x, rows):
    flat = x.reshape(-1).astype(x.dtype)
    pad = rows * _LANES - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES)


def _fused_leaf(p, g, m, v, lr, c1, c2, *, b1, b2, eps, wd, interpret):
    """One leaf -> (delta, new_m, new_v). m/v are fp32, p/g any dtype."""
    n = p.size
    if n < _MIN_KERNEL_SIZE:
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1.0 - b1) * gf
        v2 = b2 * v + (1.0 - b2) * gf * gf
        delta = -lr * (m2 * c1 / (jnp.sqrt(v2 * c2) + eps)
                       + wd * p.astype(jnp.float32))
        return delta.astype(p.dtype), m2, v2

    rows = pl.cdiv(n, _LANES)
    rows = pl.cdiv(rows, _SUBLANES) * _SUBLANES
    block_rows = min(rows, _MAX_BLOCK_ROWS)
    # Round rows UP to a block multiple (padding is free — _pad_2d zero-fills)
    # rather than shrinking the block, which would fragment the grid into
    # tiny tiles for awkward row counts.
    rows = pl.cdiv(rows, block_rows) * block_rows
    grid = (rows // block_rows,)
    tile = pl.BlockSpec(
        (block_rows, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    delta, nm, nv = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[scalar, scalar, scalar, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        # The flat p/m/v buffers are step-local copies (concatenated from
        # the leaves) that die at this call — alias them into the
        # same-shaped outputs so the kernel updates in place instead of
        # holding 3 extra param-sized buffers live. Found by
        # AOT_TPU_CHECK's memory analysis (round 5): ViT-L's update held
        # ~11x params of scratch, 17.9 GB peak at the bench batch — over
        # the v5e's 16 GB — of which this aliasing removes ~3x params.
        # (Indices are positions in the full input list, scalars included:
        # p=3 -> delta, m=5 -> new_m, v=6 -> new_v; dtypes/shapes match.)
        input_output_aliases={3: 0, 5: 1, 6: 2},
        name="fused_adamw",
        interpret=interpret,
    )(
        jnp.asarray(lr, jnp.float32).reshape(1, 1),
        jnp.asarray(c1, jnp.float32).reshape(1, 1),
        jnp.asarray(c2, jnp.float32).reshape(1, 1),
        _pad_2d(p, rows),
        _pad_2d(g, rows),
        _pad_2d(m, rows),
        _pad_2d(v, rows),
    )
    unpad = lambda x, dt: x.reshape(-1)[:n].reshape(p.shape).astype(dt)  # noqa: E731
    return unpad(delta, p.dtype), unpad(nm, jnp.float32), unpad(nv, jnp.float32)


class FusedAdamWState(NamedTuple):
    count: jax.Array  # int32 step counter
    mu: optax.Updates  # fp32 first moments, params-shaped
    nu: optax.Updates  # fp32 second moments, params-shaped


class FusedAdamWTransformation(NamedTuple):
    """Duck-types ``optax.GradientTransformation`` (init/update) while also
    carrying the clip threshold, so the Trainer can apply the global-norm
    clip in the auto-sharded region *before* entering the shard_map around
    the kernel (a per-shard norm would be wrong there). Global-norm clipping
    is idempotent, so the in-update clip below is then a guaranteed no-op —
    direct users of this transformation still get clipping without a chain.
    """

    init: object
    update: object
    grad_clip: float = 0.0


def stochastic_round(x, key, dtype=jnp.bfloat16):
    """fp32 -> bf16 with stochastic rounding: round up with probability
    proportional to the distance to the next representable value.

    Bit trick: bf16 is fp32's top 16 bits, so adding uniform random low-16
    bits to the fp32 bit pattern and truncating rounds each value up with
    exactly ``frac = low_bits / 2^16`` probability — unbiased in
    expectation, which is the whole point: round-to-nearest on a moment
    EMA ``mu <- b1*mu + (1-b1)*g`` deterministically drops any ``g``
    contribution below one bf16 ulp of ``mu``, and the moment stalls.
    (Used by ``train.low_precision_adamw`` for the ``bf16_full`` policy.)

    Non-finite values bypass the add (carry past the mantissa would walk
    inf into nan space) and cast directly — the health guard must see the
    same nan/inf the fp32 math produced. Values within one bf16 ulp of
    ``bf16_max`` can round up to inf; Adam moments live many orders of
    magnitude below that.
    """
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise NotImplementedError(
            f"stochastic_round targets bfloat16 (got {jnp.dtype(dtype)}): "
            "the truncation trick needs the target to be the source's "
            "high bits"
        )
    bits = jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.float32), jnp.uint32
    )
    noise = jax.random.bits(key, jnp.shape(x), jnp.uint32) & jnp.uint32(0xFFFF)
    rounded = (bits + noise) & jnp.uint32(0xFFFF0000)
    out = jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(
        jnp.bfloat16
    )
    return jnp.where(jnp.isfinite(x), out, jnp.asarray(x).astype(jnp.bfloat16))


def decay_leaf(p) -> bool:
    """THE weight-decay rule, defined once: matrices/embeddings (ndim>=2)
    decay; biases and norm scales (ndim<2) don't. Used by this kernel, by
    ``train.make_optimizer``'s optax paths, and by the parity tests."""
    return jnp.ndim(p) >= 2


def _clip_by_global_norm(grads, clip: float):
    norm = optax.global_norm(grads)
    scale = clip / jnp.maximum(norm, clip)
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)


def fused_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    *,
    grad_clip: float = 0.0,
    interpret: bool | None = None,
) -> optax.GradientTransformation:
    """optax-compatible AdamW whose update rule is the Pallas kernel.

    ``learning_rate`` may be a float or an optax schedule. Returned updates
    are deltas (feed ``optax.apply_updates``), so it chains with clipping
    exactly like ``optax.adamw``. Prefer ``grad_clip`` here over an outer
    ``optax.chain(clip, ...)`` — a chain's tuple state hides the
    ``FusedAdamWState`` from the Trainer's shard_map dispatch and the kernel
    would fall back to the gather-everything path.
    """

    def init_fn(params):
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        return FusedAdamWState(
            count=jnp.zeros((), jnp.int32), mu=zeros,
            nu=jax.tree.map(jnp.copy, zeros),
        )

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("fused_adamw requires params")
        if grad_clip:
            grads = _clip_by_global_norm(grads, grad_clip)
        ip = _default_interpret() if interpret is None else interpret
        # optax convention: the schedule sees the pre-increment count, the
        # bias correction the post-increment one.
        lr = (
            learning_rate(state.count)
            if callable(learning_rate)
            else learning_rate
        )
        count = state.count + 1
        t = count.astype(jnp.float32)
        c1 = 1.0 / (1.0 - jnp.power(b1, t))
        c2 = 1.0 / (1.0 - jnp.power(b2, t))

        # ONE kernel launch per (param dtype, decay group): all kernel-sized
        # leaves of a group are flattened into a single (rows, 128) buffer
        # (so at most two launches per dtype when weight_decay > 0 — decayed
        # matrices vs undecayed norm scales). A per-leaf pallas_call would
        # compile one kernel VARIANT per distinct leaf shape (~dozens for
        # GPT-2) and pay a launch per leaf per step; concatenation is
        # shard-local, so this composes unchanged with the Trainer's
        # shard_map dispatch over ZeRO/FSDP-sharded state.
        treedef = jax.tree.structure(params)
        p_leaves = jax.tree.leaves(params)
        g_leaves = jax.tree.leaves(grads)
        m_leaves = jax.tree.leaves(state.mu)
        v_leaves = jax.tree.leaves(state.nu)
        n = len(p_leaves)
        deltas: list = [None] * n
        nms: list = [None] * n
        nvs: list = [None] * n

        groups: dict = {}
        for i, p in enumerate(p_leaves):
            # Standard AdamW masking (decay_leaf): decaying a norm scale
            # toward zero is a regularization bug, not regularization.
            wd_i = weight_decay if decay_leaf(p) else 0.0
            if p.size < _MIN_KERNEL_SIZE:
                # A kernel launch per bias vector costs more than it saves.
                gf = g_leaves[i].astype(jnp.float32)
                m2 = b1 * m_leaves[i] + (1.0 - b1) * gf
                v2 = b2 * v_leaves[i] + (1.0 - b2) * gf * gf
                deltas[i] = (
                    -lr * (m2 * c1 / (jnp.sqrt(v2 * c2) + eps)
                           + wd_i * p.astype(jnp.float32))
                ).astype(p.dtype)
                nms[i], nvs[i] = m2, v2
            else:
                groups.setdefault((jnp.dtype(p.dtype), wd_i), []).append(i)

        bucket_elems = _BUCKET_ROWS * _LANES
        for (dtype, wd_i), idxs in groups.items():
            # Piece table at trace time: which (leaf, leaf-range) lands in
            # which bucket. Leaves larger than a bucket span several.
            by_bucket: list = []  # bucket -> [(leaf idx, leaf off, len)]
            off = 0
            for i in idxs:
                sz, lo = p_leaves[i].size, 0
                while lo < sz:
                    b, bo = divmod(off, bucket_elems)
                    if b == len(by_bucket):
                        by_bucket.append([])
                    ln = min(sz - lo, bucket_elems - bo)
                    by_bucket[b].append((i, lo, ln))
                    lo += ln
                    off += ln
            out_pieces: dict = {i: [] for i in idxs}
            for bp in by_bucket:
                flat = lambda leaves: jnp.concatenate(  # noqa: E731
                    [leaves[i].reshape(-1)[lo : lo + ln]
                     for i, lo, ln in bp]
                )
                d_f, nm_f, nv_f = _fused_leaf(
                    flat(p_leaves), flat(g_leaves),
                    flat(m_leaves), flat(v_leaves),
                    lr, c1, c2,
                    b1=b1, b2=b2, eps=eps, wd=wd_i, interpret=ip,
                )
                o = 0
                for i, lo, ln in bp:
                    out_pieces[i].append(
                        (d_f[o : o + ln], nm_f[o : o + ln], nv_f[o : o + ln])
                    )
                    o += ln
            for i in idxs:
                ds_, ms_, vs_ = zip(*out_pieces[i])
                cat = lambda xs: (  # noqa: E731
                    xs[0] if len(xs) == 1 else jnp.concatenate(xs)
                )
                shape = p_leaves[i].shape
                deltas[i] = cat(ds_).reshape(shape)
                nms[i] = cat(ms_).reshape(shape)
                nvs[i] = cat(vs_).reshape(shape)

        return treedef.unflatten(deltas), FusedAdamWState(
            count=count,
            mu=treedef.unflatten(nms),
            nu=treedef.unflatten(nvs),
        )

    return FusedAdamWTransformation(init_fn, update_fn, grad_clip)
