"""Overlapped bucketed gradient collectives + cross-replica weight-update
sharding (ROADMAP item 1).

Two transforms, composable, both expressed INSIDE the train step's
``shard_map`` body (see ``train.Trainer._overlapped_dp_step_fn``):

**Bucketing** (``train.grad_bucket_mb``): instead of raveling the whole
gradient pytree into one buffer and syncing it with a single post-backward
collective (``comms_quant.quantized_tree_all_reduce``), the pytree is
partitioned into size-targeted buckets in REVERSE flatten order — backward
produces the last layers' gradients first, so the first bucket to close is
the first whose inputs are ready — and each bucket gets its OWN collective.
The per-bucket collectives have no data dependence on each other, only on
their own bucket's gradient leaves, which is exactly the dependency
structure that lets XLA's scheduler start bucket k's all-reduce while the
backward dots for buckets k+1.. are still running. ``tests/test_overlap.py``
asserts this at the HLO level: the scheduled module shows the bucket
collectives issued between backward fusions, not as one terminal sync block.

**Weight-update sharding** (``train.update_sharding = 'sharded'``): the
reduce-scatter → shard-local optimizer update → all-gather transform of
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md, arXiv 2004.13336). Each bucket's gradient sync
becomes a reduce-scatter (1/n the all-reduce's second phase), every dp
member advances optimizer state for only its 1/n flat shard of the bucket
(optimizer state lives PERMANENTLY in that flat-shard layout — ZeRO-1
taken to its logical end), and a per-bucket all-gather rebuilds the
replicated params for the next forward. HLO proof obligation: the step
contains reduce-scatter + all-gather over 'dp' and NO full-gradient
all-reduce.

Wire formats compose: fp32 buckets use ``lax.psum`` / ``lax.psum_scatter``
(one XLA collective per bucket); bf16/int8 buckets ride the
``comms_quant`` block codec's ring with a per-bucket error-feedback
residual (``TrainState.grad_residual`` becomes a tuple of per-bucket
``[dp, padded]`` buffers instead of a per-parameter tree).

Everything here is static layout math plus collectives; all collective
entry points must be called inside ``shard_map`` over the named axis.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .comms_quant import (
    DEFAULT_BLOCK_SIZE,
    _compress,
    _decompress,
    quantized_all_reduce_flat,
    quantized_reduce_scatter_flat,
)

UPDATE_SHARDING_MODES: tuple[str, ...] = ("replicated", "sharded")


# ---------------------------------------------------------------------------
# Bucket layout: static partition of a param/grad pytree
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static partition of a pytree's leaves into flat, padded buckets.

    ``buckets[b]`` lists leaf indices (into the tree's canonical flatten
    order) in REVERSE order: bucket 0 holds the highest-index leaves — the
    last layers, whose gradients backward produces first. Every bucket's
    flat f32 buffer is zero-padded to ``padded_sizes[b]``, a multiple of
    ``n_members * block_size`` so it divides evenly both into the ring's
    per-member chunks and into the codec's quantization blocks (padding is
    at most one chunk row of waste and its gradient is identically zero).
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    buckets: tuple[tuple[int, ...], ...]
    padded_sizes: tuple[int, ...]
    n_members: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def sizes(self) -> tuple[int, ...]:
        return tuple(math.prod(s) for s in self.shapes)

    @property
    def shard_sizes(self) -> tuple[int, ...]:
        """Per-member flat-shard length of each bucket."""
        return tuple(p // self.n_members for p in self.padded_sizes)

    def bucket_flat(self, tree) -> list[jax.Array]:
        """Tree -> one flat padded f32 buffer per bucket."""
        leaves = self.treedef.flatten_up_to(tree)
        out = []
        for b, idxs in enumerate(self.buckets):
            flat = jnp.concatenate(
                [leaves[i].reshape(-1).astype(jnp.float32) for i in idxs]
            )
            pad = self.padded_sizes[b] - flat.shape[0]
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
            out.append(flat)
        return out

    def unbucket(self, flats) -> Any:
        """Inverse of :meth:`bucket_flat` — original shapes AND dtypes (the
        padding tail is dropped)."""
        sizes = self.sizes
        leaves: list = [None] * len(self.shapes)
        for b, idxs in enumerate(self.buckets):
            off = 0
            for i in idxs:
                seg = lax.slice_in_dim(flats[b], off, off + sizes[i])
                leaves[i] = seg.reshape(self.shapes[i]).astype(self.dtypes[i])
                off += sizes[i]
        return jax.tree.unflatten(self.treedef, leaves)

    def local_shards(self, tree, member_index) -> tuple[jax.Array, ...]:
        """Member ``member_index``'s 1/n flat slice of each bucket — the
        chunk ``lax.psum_scatter(tiled=True)`` assigns it. ``member_index``
        may be traced (``lax.axis_index``); slice sizes are static."""
        shard = self.shard_sizes
        return tuple(
            lax.dynamic_slice_in_dim(f, member_index * shard[b], shard[b])
            for b, f in enumerate(self.bucket_flat(tree))
        )

    def stacked_shards(self, tree) -> tuple[jax.Array, ...]:
        """Global ``[n_members, shard]`` view of every member's flat shard
        per bucket — what ``tx.init`` consumes for the flat-shard optimizer
        state (row ``i`` is member ``i``'s shard), OUTSIDE shard_map."""
        return tuple(
            f.reshape(self.n_members, -1) for f in self.bucket_flat(tree)
        )

    def wire_bytes(self, mode: str, block_size: int = DEFAULT_BLOCK_SIZE):
        """Per-bucket wire payload bytes of one sync under ``mode`` (the
        f32 padded size scaled by the codec's compression ratio)."""
        from .comms_quant import compression_ratio

        r = compression_ratio(mode, block_size)
        return tuple(int(p * 4 * r) for p in self.padded_sizes)


def build_bucket_layout(
    tree,
    bucket_mb: float,
    *,
    n_members: int,
    block_size: int = DEFAULT_BLOCK_SIZE,
) -> BucketLayout:
    """Greedy reverse-order packing: walk leaves from the LAST flatten index
    down, close a bucket as soon as its accumulated f32 bytes reach
    ``bucket_mb`` MiB. ``bucket_mb <= 0`` means one bucket holding
    everything (still reverse order) — the unbucketed-but-shardable layout
    ``update_sharding='sharded'`` uses when no bucket size is set.

    Works on concrete arrays, tracers, or ShapeDtypeStructs — only shapes
    and dtypes are read.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot bucket an empty pytree")
    shapes = tuple(tuple(jnp.shape(l)) for l in leaves)
    dtypes = tuple(jnp.dtype(getattr(l, "dtype", jnp.result_type(l))) for l in leaves)
    target = float("inf") if bucket_mb <= 0 else bucket_mb * 2**20
    buckets: list[tuple[int, ...]] = []
    cur: list[int] = []
    cur_bytes = 0.0
    for i in reversed(range(len(leaves))):
        cur.append(i)
        cur_bytes += math.prod(shapes[i]) * 4
        if cur_bytes >= target:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0.0
    if cur:
        buckets.append(tuple(cur))
    multiple = n_members * block_size
    padded = tuple(
        max(multiple, -(-sum(math.prod(shapes[i]) for i in b) // multiple) * multiple)
        for b in buckets
    )
    return BucketLayout(
        treedef=treedef,
        shapes=shapes,
        dtypes=dtypes,
        buckets=tuple(buckets),
        padded_sizes=padded,
        n_members=n_members,
    )


# ---------------------------------------------------------------------------
# Per-bucket error feedback
# ---------------------------------------------------------------------------


def _ef_flat(flat, res, mode: str, block_size: int):
    """EF-SGD on one already-padded flat bucket: compress ``flat + res``,
    return ``(decompressed, new_res)`` where ``new_res`` is exactly the
    compression error (``comms_quant.ef_compress`` semantics, minus the
    ravel — buckets are already flat). ``res=None`` / fp32 wire: EF off."""
    if res is None or mode == "fp32":
        return flat, res
    total = flat + res
    sent = _decompress(_compress(total, mode, block_size), mode)
    return sent, total - sent


def zeros_bucket_residuals(layout: BucketLayout, n_devices: int):
    """Per-bucket EF residual buffers, zeros: one ``[n_devices, padded]``
    f32 array per bucket. Leading dim = per-member (sharded over 'dp', like
    the per-parameter residual tree — ``parallel/zero.residual_shardings``
    handles any leaf with a leading device dim)."""
    return tuple(
        jnp.zeros((n_devices, p), jnp.float32) for p in layout.padded_sizes
    )


# ---------------------------------------------------------------------------
# Bucketed collectives (call inside shard_map)
# ---------------------------------------------------------------------------


def bucketed_all_reduce(
    grads,
    layout: BucketLayout,
    axis: str,
    *,
    mode: str = "fp32",
    block_size: int = DEFAULT_BLOCK_SIZE,
    residuals=None,
):
    """All-reduce-sum a gradient pytree as one INDEPENDENT collective per
    bucket. Returns ``(summed_tree, new_residuals)`` — divide by axis size
    for the mean. fp32 wire: a plain ``lax.psum`` per bucket (K independent
    all-reduce ops in the HLO, each depending only on its bucket's grads —
    the schedulable-overlap structure). Lossy wire: the ``comms_quant``
    compressed ring per bucket, with per-bucket EF via ``residuals`` (a
    sequence of flat per-bucket buffers, or None for no EF)."""
    out = []
    new_res = []
    for b, flat in enumerate(layout.bucket_flat(grads)):
        res = residuals[b] if residuals is not None else None
        sent, r = _ef_flat(flat, res, mode, block_size)
        if mode == "fp32":
            summed = lax.psum(sent, axis)
        else:
            summed = quantized_all_reduce_flat(
                sent, axis, mode=mode, block_size=block_size
            )
        out.append(summed)
        new_res.append(r)
    return layout.unbucket(out), (
        tuple(new_res) if residuals is not None else None
    )


def bucketed_reduce_scatter(
    grads,
    layout: BucketLayout,
    axis: str,
    *,
    mode: str = "fp32",
    block_size: int = DEFAULT_BLOCK_SIZE,
    residuals=None,
):
    """Reduce-scatter a gradient pytree per bucket: member ``i`` gets flat
    chunk ``i`` of each bucket's sum (``lax.psum_scatter(tiled=True)``
    semantics, matching :meth:`BucketLayout.local_shards`). Returns
    ``(shards, new_residuals)`` with ``shards`` a tuple of per-bucket
    ``[padded/n]`` vectors."""
    shards = []
    new_res = []
    for b, flat in enumerate(layout.bucket_flat(grads)):
        res = residuals[b] if residuals is not None else None
        sent, r = _ef_flat(flat, res, mode, block_size)
        if mode == "fp32":
            shard = lax.psum_scatter(sent, axis, scatter_dimension=0, tiled=True)
        else:
            shard = quantized_reduce_scatter_flat(
                sent, axis, mode=mode, block_size=block_size
            )
        shards.append(shard)
        new_res.append(r)
    return tuple(shards), (tuple(new_res) if residuals is not None else None)


def all_gather_buckets(shards, layout: BucketLayout, axis: str):
    """Reassemble the full (replicated) param tree from every member's
    fresh flat shards: one tiled all-gather per bucket, then unbucket.
    The sharded-update path's param refresh — always full-precision wire
    (params, unlike grads, have no error-feedback channel to absorb a
    lossy gather)."""
    flats = [lax.all_gather(s, axis, tiled=True) for s in shards]
    return layout.unbucket(flats)


# ---------------------------------------------------------------------------
# Config-time fences
# ---------------------------------------------------------------------------


def check_update_sharding_config(
    *,
    update_sharding: str,
    grad_bucket_mb: float = 0.0,
    optim_name: str | None = None,
    weight_decay: float = 0.0,
    grad_clip: float = 0.0,
) -> None:
    """Optimizer-level composition fences for the overlap knobs — the
    checks ``Trainer.__init__`` cannot do because it sees an opaque
    ``optax.GradientTransformation`` (``cli.build_all`` calls this with
    the config's optimizer fields before building anything).

    The sharded update runs ``tx.update`` on flat 1-D per-bucket shards, so
    every per-leaf-shape optimizer feature is structurally lost there:

    - ``weight_decay > 0``: the shared decay mask (``fused_adamw.
      decay_leaf``) is shape-based — every flat shard looks like a bias and
      would silently skip decay;
    - ``grad_clip > 0``: ``optax.clip_by_global_norm`` inside the chain
      would clip by each member's LOCAL shard norm, not the global norm;
    - ``adamw_fused``: the Pallas kernel's ``FusedAdamWState`` dispatch
      (``Trainer._tx_update``) has its own shard_map and per-leaf specs.
    """
    if update_sharding not in UPDATE_SHARDING_MODES:
        raise ValueError(
            f"train.update_sharding={update_sharding!r} not in "
            f"{UPDATE_SHARDING_MODES}"
        )
    if grad_bucket_mb < 0:
        raise ValueError(
            f"train.grad_bucket_mb={grad_bucket_mb} must be >= 0 "
            "(0 = single bucket / feature off)"
        )
    if update_sharding != "sharded":
        return
    if optim_name == "adamw_fused":
        raise NotImplementedError(
            "update_sharding='sharded' x optim.name='adamw_fused' is "
            "unsupported in v1: the fused kernel dispatches through its own "
            "per-leaf shard_map (Trainer._tx_update), which the flat-shard "
            "update replaces — use optim.name='adamw' or "
            "update_sharding='replicated'"
        )
    if weight_decay:
        raise NotImplementedError(
            f"update_sharding='sharded' x optim.weight_decay={weight_decay} "
            "is unsupported in v1: the decay mask is per-leaf-shape "
            "(fused_adamw.decay_leaf) and flat 1-D gradient shards would "
            "silently skip decay — use weight_decay=0.0 or "
            "update_sharding='replicated'"
        )
    if grad_clip:
        raise NotImplementedError(
            f"update_sharding='sharded' x optim.grad_clip={grad_clip} is "
            "unsupported in v1: clip_by_global_norm inside the optimizer "
            "chain would clip by the LOCAL shard norm — use grad_clip=0.0 "
            "or update_sharding='replicated'"
        )
