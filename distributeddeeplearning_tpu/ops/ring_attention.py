"""Ring attention — context parallelism over the ``cp`` mesh axis.

The TPU-native long-context mechanism (SURVEY.md §5): queries stay put,
sharded over the sequence dim on the ``cp`` ICI ring; KV blocks rotate one
neighbor per step via ``lax.ppermute`` while each device accumulates its
queries' attention over the visiting blocks with the online-softmax
(flash-attention) recurrence. Peak memory per device is O(L/cp) activations
and one KV block; comm volume per step is one KV block over ICI, which the
XLA latency-hiding scheduler overlaps with the block matmuls.

This is the pure-``shard_map``+``lax.scan`` reference implementation — it
runs on the CPU simulator and is the correctness oracle for the fused Pallas
variant. Works under ``jax.grad`` (scan/ppermute are differentiable; the
backward pass rotates blocks in the opposite direction).

Causal masking across blocks: device i's queries own global positions
``[i*Lq, (i+1)*Lq)``; each rotation receives from the +1 neighbor, so at ring
step t device i sees the KV block of device ``(i + t) mod cp`` — blocks from
lower-indexed devices are fully visible, higher-indexed fully masked, the
diagonal block gets the local causal mask. Fully-masked blocks contribute
exactly zero via the validity mask (not just -inf scores, which would break
the online-softmax normalization).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..mesh import BATCH_AXES


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool):
    """Per-device body (runs inside shard_map).

    q, k, v: [batch, seq_local, heads, head_dim] — this device's blocks.
    Returns [batch, seq_local, heads, head_dim].
    """
    cp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale

    # Online-softmax carries (all fp32): running max m, denominator l,
    # weighted value accumulator acc. Built from qf (not jnp.zeros) so they
    # carry q's varying-manual-axes type — scan requires carry in/out types
    # to match inside shard_map.
    zeros = jnp.zeros_like(qf[..., 0]).transpose(0, 2, 1)  # [b, h, lq]
    m0 = zeros - 1e30
    l0 = zeros
    acc0 = jnp.zeros_like(qf).transpose(0, 2, 1, 3)  # [b, h, lq, d]

    # Local causal mask for the diagonal block; relative block position
    # decides full/empty visibility otherwise.
    tril = jnp.tril(jnp.ones((lq, lq), bool))

    def block_update(m, l, acc, kt, vt, t):
        # Whose KV block is visiting: each rotation receives from the +1
        # neighbor, so at step t device idx holds block (idx + t) mod cp.
        src = (idx + t) % cp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kt.astype(jnp.float32))
        if causal:
            block_mask = jnp.where(src == idx, tril[None, None], src < idx)
        else:
            block_mask = jnp.ones((1, 1, lq, lq), bool)
        # Mask BEFORE exponentiating — and before the subtraction, so the
        # masked branch never materializes exp(large): exp(-inf - m) == 0
        # exactly, and the where's transpose zeroes the masked cotangents
        # (masking only the exp's *output* leaves an inf in the backward
        # graph: 0 * inf = NaN grads once any masked score exceeds the
        # visible row max by ~88).
        s_masked = jnp.where(block_mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s_masked.max(-1))
        p = jnp.exp(s_masked - m_new[..., None])
        rescale = jnp.exp(m - m_new)
        l = l * rescale + p.sum(-1)
        acc = acc * rescale[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vt.astype(jnp.float32)
        )
        return m_new, l, acc

    def step(carry, t):
        m, l, acc, kt, vt = carry
        m, l, acc = block_update(m, l, acc, kt, vt, t)
        # Rotate KV one step around the ring (receive from the +1 neighbor).
        perm = [(i, (i - 1) % cp) for i in range(cp)]
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return (m, l, acc, kt, vt), None

    # Scan the first cp-1 blocks (each ends with a rotation), then peel the
    # final block so its KV rotation — whose result nothing consumes — is
    # never emitted (XLA can't DCE a collective inside a scan body).
    (m, l, acc, kt, vt), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(cp - 1)
    )
    m, l, acc = block_update(m, l, acc, kt, vt, cp - 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [b, h, lq, d]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    q, k, v, mesh: Mesh, *, causal: bool = True, axis_name: str = "cp"
):
    """Global-array entry point: shard_map the ring body over the mesh.

    q/k/v: [batch, seq, heads, head_dim] global arrays; batch is expected
    sharded over BATCH_AXES, seq over ``axis_name``, heads over 'tp' (the
    standard activation layout from ``sharding.py``). Composes with DP/FSDP/TP
    because those axes appear in the in/out specs and are untouched inside.
    """
    from ..parallel.sp_ring import check_ring_shapes

    check_ring_shapes(q.shape[1], mesh.shape[axis_name])
    if q.shape[2] % mesh.shape["tp"]:
        raise ValueError(
            f"ring: heads={q.shape[2]} not divisible by tp={mesh.shape['tp']}"
        )
    spec = P(BATCH_AXES, axis_name, "tp", None)
    fn = jax.shard_map(
        lambda q, k, v: _ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
