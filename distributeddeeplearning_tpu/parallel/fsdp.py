"""FSDP / ZeRO-3-style parameter sharding — also purely a placement decision.

Parameters' ``embed`` dimension is mapped to the ``fsdp`` mesh axis by
``DEFAULT_LOGICAL_RULES``; the batch is sharded over ``('dp','fsdp')`` jointly,
so the ``fsdp`` axis acts as data parallelism whose parameter storage is
sharded. XLA's SPMD partitioner then emits, per layer, the all-gather of that
layer's params before use and the reduce-scatter of its grads after — the
ZeRO-3 communication schedule — without any gather/scatter code here. The
latency-hiding scheduler overlaps those collectives with compute.

There is no rules preset to apply: FSDP **is** ``DEFAULT_LOGICAL_RULES`` with
``fsdp > 1`` in the mesh. In particular the embedding table (usually the
largest parameter) is already sharded on BOTH its dims under the defaults —
vocab over ``tp`` and embed over ``fsdp`` — so no extra vocab rule is needed.
(A rule like ``vocab=('tp','fsdp')`` would actually *lose* the tp sharding:
flax drops a composite rule entirely when any of its mesh axes is already
taken by another dim of the same array.)

ZeRO-1 (optimizer-state-only sharding, reference workload 4) lives in
``zero.py``; combining ``fsdp>1`` with ``zero1=True`` shards *everything*.

This module also owns the sharding *inspection* helpers every strategy test
uses to prove placement is real (loss parity alone passes with silently
replicated state — the round-2 lesson).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding


def sharded_fraction(tree, axis: str) -> float:
    """Fraction of the tree's elements whose sharding uses ``axis``.

    The load-bearing assertion for "is TP/FSDP actually on": parity tests can
    pass with silently-replicated params, so tests also require
    ``sharded_fraction(params, 'tp') > threshold``.
    """
    total = 0
    sharded = 0
    for leaf in jax.tree.leaves(tree):
        n = math.prod(getattr(leaf, "shape", ()) or (1,))
        total += n
        s = getattr(leaf, "sharding", None)
        # Naming the axis is not enough — over a size-1 mesh axis the spec
        # entry is a placement no-op and the leaf is in fact replicated.
        if (
            isinstance(s, NamedSharding)
            and _spec_uses(s.spec, axis)
            and s.mesh.shape[axis] > 1
        ):
            sharded += n
    return sharded / max(total, 1)


def _spec_uses(spec, axis: str) -> bool:
    for entry in spec:
        axes = entry if isinstance(entry, tuple) else (entry,)
        if axis in axes:
            return True
    return False


def grad_sync_bytes(
    tree,
    *,
    mode: str = "fp32",
    block_size: int = 256,
    n_members: int = 2,
    wire_elem_bytes: float | None = None,
) -> int:
    """Per-member wire bytes of one data-parallel gradient sync of ``tree``.

    The analytic counterpart of the HLO measurement (``utils/hlo.py``): a
    ring all-reduce over ``n`` members ships ``2*(n-1)`` hops of ``P/n``
    elements each, at the payload width of the ``grad_comm`` mode
    (comms_quant: int8 values + one f32 scale per ``block_size`` — ~4x under
    fp32), so the byte cut per mode is visible without an HLO dump.

    ``wire_elem_bytes`` overrides the uncompressed element width — under a
    mixed-precision policy grads leave the backward pass in the compute
    dtype, so the fp32-mode all-reduce actually ships 2 B/elem (the
    compressed modes already quantize from whatever width arrives, so their
    scale/value payload is unchanged).
    """
    from ..comms_quant import compression_ratio

    n_elems = sum(
        math.prod(getattr(leaf, "shape", ()) or (1,))
        for leaf in jax.tree.leaves(tree)
    )
    per_hop = -(-n_elems // n_members)  # ceil: ring chunks are padded equal
    if mode == "fp32" and wire_elem_bytes is not None:
        bytes_per_elem = float(wire_elem_bytes)
    else:
        bytes_per_elem = 4.0 * compression_ratio(mode, block_size)
    return int(2 * (n_members - 1) * per_hop * bytes_per_elem)


def per_device_bytes(tree) -> int:
    """Actual per-device HBM footprint of a sharded pytree (sum of addressable
    shard bytes on device 0's shards)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "addressable_shards"):
            shard = leaf.addressable_shards[0]
            total += shard.data.nbytes
        elif hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total
