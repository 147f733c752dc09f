"""Hierarchical ICI+DCN gradient collectives (ROADMAP item 3).

A hybrid mesh (``mesh.dcn_dp > 1``) lays the data-parallel axis out as
``dcn_dp`` slices of ``ici_size = dp / dcn_dp`` chips each: members within a
slice talk over ICI (fast intra-slice torus), members in the same position of
different slices talk over DCN (slow cross-slice network). A FLAT gradient
all-reduce over that axis ships the FULL payload across DCN; the standard
multi-slice decomposition (arXiv 1909.09756 "Scale MLPerf-0.6 models on
Google TPU-v3 Pods"; arXiv 2204.06514) cuts the DCN bytes by ``ici_size``:

1. **intra-slice reduce-scatter** over the ICI sub-groups — each member ends
   up with a 1/ici_size shard of its slice's partial sum (full payload, but
   all on ICI);
2. **cross-slice all-reduce** of that shard over the DCN sub-groups — the
   only DCN traffic, ``payload / ici_size`` bytes;
3. **intra-slice all-gather** to rebuild the replicated sum (ICI again).

Implemented with ``axis_index_groups`` on the named-axis collectives, so the
compiled HLO literally shows a reduce-scatter/all-gather whose replica groups
are the ICI sub-groups and an all-reduce whose replica groups span only
cross-slice peers (``tests/test_hier.py`` pins payloads and group shapes).

**Member numbering contract** (matches ``mesh_utils.create_hybrid_device_mesh``
and the CPU-sim reshape in ``mesh.build_mesh``: DCN outermost): dp member
``i`` sits in slice ``d = i // ici`` at slice-local position ``j = i % ici``.

**Sharded update** (``train.update_sharding='sharded'``): the cross-slice
step becomes a reduce-scatter too, leaving member ``(d, j)`` with ONE
1/dp chunk of the global sum — but a PERMUTED one: chunk
``j * dcn + d`` (intra-slice scatter splits by ``j`` first, the cross-slice
scatter then splits each intra-shard by ``d``). :meth:`HierTopology.chunk_index`
is that permutation; the param refresh reverses it with a two-phase
all-gather (cross, then intra). The flat ``[dp, shard]`` optimizer state
needs no init-time shuffle — moments are born zero and row ``i`` simply
*means* chunk ``chunk_index(i)`` for the life of the run (a checkpoint is
therefore tied to its ``comm_hierarchy`` setting, like it is to ``dp``).

**Wire formats** compose exactly as in ``comms_overlap``: fp32 buckets use
the grouped ``lax`` collectives; bf16/int8 buckets ride the ``comms_quant``
block codec on GROUPED ``ppermute`` rings (intra ring among slice-local
neighbors, cross ring among same-position peers), with error feedback
applied ONCE per bucket before the first hop — the per-bucket
``[dp, padded]`` residual schema of ``comms_overlap`` is unchanged.

**Numerics**: hierarchical summation re-associates the fp32 sum
(within-slice first, then across slices), so results agree with the flat
all-reduce to fp32 rounding, NOT bitwise — ``tests/test_hier.py`` pins the
exact hierarchical association against a numpy oracle bitwise instead, and
flat-vs-hierarchical training losses at fp32 tolerance.

All collective entry points must be called inside ``shard_map`` over the
named axis.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from .comms_overlap import BucketLayout, _ef_flat
from .comms_quant import DEFAULT_BLOCK_SIZE, _compress, _decompress

HIERARCHY_MODES: tuple[str, ...] = ("flat", "hierarchical", "auto")


@dataclasses.dataclass(frozen=True)
class HierTopology:
    """Static shape of the hierarchical decomposition: ``n`` dp members in
    ``dcn`` slices of ``ici = n // dcn`` chips. Pure index math — safe to
    build anywhere, including inside traced code."""

    n: int
    dcn: int

    def __post_init__(self):
        if self.dcn < 2:
            raise ValueError(
                f"HierTopology needs dcn >= 2 (got {self.dcn}): with one "
                "slice there is no cross-slice phase to split off"
            )
        if self.n % self.dcn:
            raise ValueError(
                f"dp={self.n} not divisible by dcn_dp={self.dcn}"
            )
        if self.n // self.dcn < 2:
            raise ValueError(
                f"dp={self.n} / dcn_dp={self.dcn} leaves ici_size=1: every "
                "member is its own slice and 'hierarchical' degenerates to "
                "a flat DCN all-reduce — use comm_hierarchy='flat'"
            )

    @property
    def ici(self) -> int:
        return self.n // self.dcn

    def intra_groups(self) -> tuple[tuple[int, ...], ...]:
        """ICI sub-groups: the members of each slice."""
        return tuple(
            tuple(d * self.ici + j for j in range(self.ici))
            for d in range(self.dcn)
        )

    def cross_groups(self) -> tuple[tuple[int, ...], ...]:
        """DCN sub-groups: same slice-local position across all slices."""
        return tuple(
            tuple(d * self.ici + j for d in range(self.dcn))
            for j in range(self.ici)
        )

    def chunk_index(self, member_index):
        """Global 1/n chunk owned by dp member ``i`` after intra-slice THEN
        cross-slice reduce-scatter: ``(i % ici) * dcn + i // ici``. Works on
        ints and traced indices alike."""
        return (member_index % self.ici) * self.dcn + member_index // self.ici

    def intra_perm(self) -> list[tuple[int, int]]:
        """ppermute ring within each slice: ``(d,j) -> (d, j+1 mod ici)``."""
        return [
            (d * self.ici + j, d * self.ici + (j + 1) % self.ici)
            for d in range(self.dcn)
            for j in range(self.ici)
        ]

    def cross_perm(self) -> list[tuple[int, int]]:
        """ppermute ring across slices: ``(d,j) -> (d+1 mod dcn, j)``."""
        return [
            (d * self.ici + j, ((d + 1) % self.dcn) * self.ici + j)
            for d in range(self.dcn)
            for j in range(self.ici)
        ]


def resolve_hierarchy(comm_hierarchy: str, dcn_dp: int) -> bool:
    """Whether the hierarchical path is active: explicit 'hierarchical', or
    'auto' on a hybrid mesh (``dcn_dp > 1``). 'flat' never."""
    if comm_hierarchy not in HIERARCHY_MODES:
        raise ValueError(
            f"train.comm_hierarchy={comm_hierarchy!r} not in "
            f"{HIERARCHY_MODES}"
        )
    if comm_hierarchy == "hierarchical":
        return True
    return comm_hierarchy == "auto" and dcn_dp > 1


def check_comm_hierarchy_config(
    *, comm_hierarchy: str, dcn_dp: int, dp: int | None = None
) -> None:
    """Config-time fences for the hierarchy knobs, by name (cli.build_all
    calls this before any build; Trainer.__init__ re-checks with the real
    mesh dp). Illegal: unknown mode; 'hierarchical' with one slice
    (nothing to hierarchize); a slice count that doesn't divide dp; and the
    ici_size == 1 degenerate (every member its own slice)."""
    if dcn_dp < 1:
        raise ValueError(f"mesh.dcn_dp={dcn_dp} must be >= 1")
    use = resolve_hierarchy(comm_hierarchy, dcn_dp)
    if comm_hierarchy == "hierarchical" and dcn_dp == 1:
        raise ValueError(
            "train.comm_hierarchy='hierarchical' requires mesh.dcn_dp > 1: "
            "with a single slice there is no cross-slice phase — use "
            "'flat' or 'auto'"
        )
    if use and dp is not None:
        # Raises by name on non-dividing dcn_dp and on ici_size == 1.
        HierTopology(n=dp, dcn=dcn_dp)


# ---------------------------------------------------------------------------
# fp32 hierarchical collectives (grouped lax ops; call inside shard_map)
# ---------------------------------------------------------------------------


def hier_psum(flat, axis: str, topo: HierTopology):
    """Hierarchical all-reduce-sum of a flat buffer: intra-slice
    reduce-scatter -> cross-slice all-reduce of the 1/ici shard -> intra
    all-gather. Same result as ``lax.psum`` up to fp32 re-association.
    ``flat.shape[0]`` must divide by ``topo.ici``."""
    shard = lax.psum_scatter(
        flat, axis, scatter_dimension=0, tiled=True,
        axis_index_groups=topo.intra_groups(),
    )
    shard = lax.psum(shard, axis, axis_index_groups=topo.cross_groups())
    return lax.all_gather(
        shard, axis, tiled=True, axis_index_groups=topo.intra_groups()
    )


def hier_psum_scatter(flat, axis: str, topo: HierTopology):
    """Hierarchical reduce-scatter: intra-slice scatter then cross-slice
    scatter. Member ``i`` ends with global chunk ``topo.chunk_index(i)`` of
    the sum (NOT chunk ``i`` — see the module docstring)."""
    shard = lax.psum_scatter(
        flat, axis, scatter_dimension=0, tiled=True,
        axis_index_groups=topo.intra_groups(),
    )
    return lax.psum_scatter(
        shard, axis, scatter_dimension=0, tiled=True,
        axis_index_groups=topo.cross_groups(),
    )


def hier_all_gather(shard, axis: str, topo: HierTopology):
    """Inverse of :func:`hier_psum_scatter`'s placement: cross-slice
    all-gather first (rebuilds each member's contiguous intra-shard
    ``[j*p/ici, (j+1)*p/ici)``), then intra-slice all-gather (rebuilds the
    full buffer in order)."""
    intra_shard = lax.all_gather(
        shard, axis, tiled=True, axis_index_groups=topo.cross_groups()
    )
    return lax.all_gather(
        intra_shard, axis, tiled=True, axis_index_groups=topo.intra_groups()
    )


# ---------------------------------------------------------------------------
# Quantized hierarchical collectives (grouped ppermute rings)
# ---------------------------------------------------------------------------


def _grouped_hop(payload, axis: str, perm):
    return tuple(lax.ppermute(p, axis, perm=perm) for p in payload)


def _grouped_ring_reduce(
    flat, axis: str, perm, size: int, local, mode: str, block_size: int
):
    """``comms_quant._ring_reduce_phase`` generalized to a ring restricted
    to groups of ``size`` members: ``perm`` is the grouped neighbor
    permutation, ``local`` the member's index WITHIN its group. Returns the
    fully reduced chunk ``(local + 1) % size`` (the standard ring layout)."""
    chunks = flat.reshape(size, -1)
    partial = lax.dynamic_slice_in_dim(chunks, local, 1, axis=0)[0]
    for s in range(size - 1):
        payload = _grouped_hop(_compress(partial, mode, block_size), axis, perm)
        received = _decompress(payload, mode)
        idx = (local - 1 - s) % size
        partial = received + lax.dynamic_slice_in_dim(chunks, idx, 1, axis=0)[0]
    return partial


def _grouped_ring_all_reduce(
    flat, axis: str, perm, size: int, local, mode: str, block_size: int
):
    """Grouped quantized ring all-reduce (reduce phase + compressed gather
    phase) — ``comms_quant.quantized_all_reduce_flat`` on a sub-group."""
    partial = _grouped_ring_reduce(
        flat, axis, perm, size, local, mode, block_size
    )
    payload = _compress(partial, mode, block_size)
    out = jnp.zeros_like(partial.reshape(1, -1).repeat(size, 0))
    own_idx = (local + 1) % size
    out = lax.dynamic_update_slice_in_dim(
        out, _decompress(payload, mode)[None], own_idx, axis=0
    )
    for s in range(size - 1):
        payload = _grouped_hop(payload, axis, perm)
        idx = (local - s) % size
        out = lax.dynamic_update_slice_in_dim(
            out, _decompress(payload, mode)[None], idx, axis=0
        )
    return out.reshape(-1)


def _grouped_ring_reduce_scatter(
    flat, axis: str, perm, size: int, local, mode: str, block_size: int
):
    """Grouped quantized ring reduce-scatter: one extra compressed hop moves
    the ring-final chunk to its owner (member ``local`` gets chunk
    ``local``)."""
    partial = _grouped_ring_reduce(
        flat, axis, perm, size, local, mode, block_size
    )
    payload = _grouped_hop(_compress(partial, mode, block_size), axis, perm)
    return _decompress(payload, mode)


def _hier_indices(axis: str, topo: HierTopology):
    """(slice-local j, slice d) of the calling member — traced."""
    i = lax.axis_index(axis)
    return i % topo.ici, i // topo.ici


def hier_quantized_all_reduce_flat(
    flat, axis: str, topo: HierTopology, *, mode: str,
    block_size: int = DEFAULT_BLOCK_SIZE,
):
    """Quantized hierarchical all-reduce: intra quantized ring
    reduce-scatter -> cross quantized ring all-reduce of the 1/ici shard ->
    intra compressed-circulate all-gather. ``flat.shape[0]`` must be a
    multiple of ``topo.n * block_size`` (bucket padding guarantees it)."""
    j, d = _hier_indices(axis, topo)
    # Intra reduce-scatter: member (d, j) reduces its slice's chunk j.
    shard = _grouped_ring_reduce_scatter(
        flat, axis, topo.intra_perm(), topo.ici, j, mode, block_size
    )
    # Cross all-reduce among same-position peers (the only DCN traffic).
    shard = _grouped_ring_all_reduce(
        shard, axis, topo.cross_perm(), topo.dcn, d, mode, block_size
    )
    # Intra all-gather: circulate each member's reduced shard compressed.
    # Every member — including the shard's owner — uses the decompressed
    # value, so the gathered buffer is bit-identical across the slice
    # (the comms_quant gather-phase discipline).
    payload = _compress(shard, mode, block_size)
    out = jnp.zeros_like(shard.reshape(1, -1).repeat(topo.ici, 0))
    out = lax.dynamic_update_slice_in_dim(
        out, _decompress(payload, mode)[None], j, axis=0
    )
    perm = topo.intra_perm()
    for s in range(topo.ici - 1):
        payload = _grouped_hop(payload, axis, perm)
        idx = (j - 1 - s) % topo.ici
        out = lax.dynamic_update_slice_in_dim(
            out, _decompress(payload, mode)[None], idx, axis=0
        )
    return out.reshape(-1)


def hier_quantized_reduce_scatter_flat(
    flat, axis: str, topo: HierTopology, *, mode: str,
    block_size: int = DEFAULT_BLOCK_SIZE,
):
    """Quantized hierarchical reduce-scatter: intra ring RS then cross ring
    RS. Member ``i`` gets global chunk ``topo.chunk_index(i)``, like
    :func:`hier_psum_scatter`."""
    j, d = _hier_indices(axis, topo)
    shard = _grouped_ring_reduce_scatter(
        flat, axis, topo.intra_perm(), topo.ici, j, mode, block_size
    )
    return _grouped_ring_reduce_scatter(
        shard, axis, topo.cross_perm(), topo.dcn, d, mode, block_size
    )


# ---------------------------------------------------------------------------
# Bucketed entry points (mirror comms_overlap's signatures)
# ---------------------------------------------------------------------------


def bucketed_hier_all_reduce(
    grads,
    layout: BucketLayout,
    axis: str,
    topo: HierTopology,
    *,
    mode: str = "fp32",
    block_size: int = DEFAULT_BLOCK_SIZE,
    residuals=None,
):
    """Hierarchical counterpart of ``comms_overlap.bucketed_all_reduce``:
    one independent 3-phase hierarchical collective per bucket, same
    ``(summed_tree, new_residuals)`` contract, same once-per-bucket error
    feedback (``residuals`` schema unchanged)."""
    out, new_res = [], []
    for b, flat in enumerate(layout.bucket_flat(grads)):
        res = residuals[b] if residuals is not None else None
        sent, r = _ef_flat(flat, res, mode, block_size)
        if mode == "fp32":
            summed = hier_psum(sent, axis, topo)
        else:
            summed = hier_quantized_all_reduce_flat(
                sent, axis, topo, mode=mode, block_size=block_size
            )
        out.append(summed)
        new_res.append(r)
    return layout.unbucket(out), (
        tuple(new_res) if residuals is not None else None
    )


def bucketed_hier_reduce_scatter(
    grads,
    layout: BucketLayout,
    axis: str,
    topo: HierTopology,
    *,
    mode: str = "fp32",
    block_size: int = DEFAULT_BLOCK_SIZE,
    residuals=None,
):
    """Hierarchical counterpart of ``comms_overlap.bucketed_reduce_scatter``.
    Member ``i``'s shard is global chunk ``topo.chunk_index(i)`` of each
    bucket — pair with ``layout.local_shards(params, topo.chunk_index(i))``
    and :func:`hier_all_gather_buckets`."""
    shards, new_res = [], []
    for b, flat in enumerate(layout.bucket_flat(grads)):
        res = residuals[b] if residuals is not None else None
        sent, r = _ef_flat(flat, res, mode, block_size)
        if mode == "fp32":
            shard = hier_psum_scatter(sent, axis, topo)
        else:
            shard = hier_quantized_reduce_scatter_flat(
                sent, axis, topo, mode=mode, block_size=block_size
            )
        shards.append(shard)
        new_res.append(r)
    return tuple(shards), (tuple(new_res) if residuals is not None else None)


def hier_all_gather_buckets(shards, layout: BucketLayout, axis: str,
                            topo: HierTopology):
    """Param refresh for the sharded update under hierarchy: two-phase
    (cross, then intra) all-gather per bucket reassembles the flat buffers
    in chunk order, then unbucket. Full-precision wire, like
    ``comms_overlap.all_gather_buckets``."""
    flats = [hier_all_gather(s, axis, topo) for s in shards]
    return layout.unbucket(flats)
