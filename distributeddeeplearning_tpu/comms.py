"""Collective-communication layer — the TPU equivalent of the reference's NCCL.

The reference (``BASELINE.json:5``) uses NCCL allreduce for gradient sync and
NCCL broadcast for parameter init, managed as explicit host-side library calls
on CUDA streams. On TPU there is no user-space transport: these wrappers are
thin conventions over ``jax.lax`` collectives that only exist *inside* a
compiled program (under ``jax.shard_map`` / ``jit`` with a mesh), where XLA
schedules them over ICI/DCN and overlaps them with compute via its
latency-hiding scheduler.

Mapping (reference -> here):
- ncclAllReduce(grads)        -> :func:`psum` / :func:`pmean` over ``dp``-like axes
- ncclReduceScatter + ZeRO    -> :func:`reduce_scatter`
- ncclAllGather               -> :func:`all_gather`
- ncclBroadcast(params, root) -> :func:`broadcast` (masked psum)
- ncclSend/Recv ring          -> :func:`ring_shift` (ppermute)
- MoE / Ulysses all-to-all    -> :func:`all_to_all`
"""

from __future__ import annotations

import functools

import jax
from jax import lax


AxisName = str | tuple[str, ...]


def psum(x, axis: AxisName):
    """All-reduce sum over ``axis`` (gradient sync; NCCL allreduce analogue)."""
    return lax.psum(x, axis)


def pmean(x, axis: AxisName):
    """All-reduce mean over ``axis`` (loss/metric aggregation)."""
    return lax.pmean(x, axis)


def all_gather(x, axis: str, *, gather_axis: int = 0, tiled: bool = True):
    """Gather shards along ``gather_axis`` from every member of ``axis``.

    ``tiled=True`` concatenates into the existing dimension (NCCL allgather
    semantics); ``tiled=False`` stacks a new leading device dimension.
    """
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0):
    """Reduce-sum over ``axis`` then scatter shards along ``scatter_axis``.

    The ZeRO-1 gradient path: each member keeps 1/N of the summed gradient.
    """
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """Transpose shards between a tensor dimension and the mesh ``axis``
    (Ulysses sequence<->head reshard; MoE token dispatch)."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def axis_index(axis: str):
    """This member's coordinate along ``axis``."""
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static size of a mesh axis, usable inside shard_map-traced code."""
    return lax.axis_size(axis)


def ring_shift(x, axis: str, *, shift: int = 1):
    """Rotate ``x`` around the ``axis`` ring: member i receives the value held
    by member ``i - shift`` (mod N). The building block of ring attention and
    pipeline communication; on TPU each hop is one ICI-neighbor ``ppermute``.
    """
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm=perm)


def broadcast(x, axis: str, *, src: int = 0):
    """Broadcast the value held by ``src`` to all members of ``axis``.

    The init-time parameter broadcast (reference: NCCL broadcast from rank 0;
    ``BASELINE.json:5`` "Parameter broadcast at init"). Implemented as a
    masked psum, which XLA lowers to an efficient collective.
    """
    idx = lax.axis_index(axis)
    masked = jax.tree.map(lambda a: jax.numpy.where(idx == src, a, 0), x)
    return lax.psum(masked, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_identity_bwd(x, axis: str):
    """``psum`` whose TRANSPOSE is the identity — the correct adjoint for a
    row-parallel layer output (``out = psum_tp(partial)``: the true
    ``d(partial)`` on every rank is the full output cotangent, once).

    Why it exists: under ``shard_map(check_vma=False)`` a raw ``lax.psum``
    inside a ``jax.vjp``'d region transposes to ANOTHER psum, multiplying
    every cotangent that crosses it by the axis size (measured in
    ``tests/test_comms.py``). The vma checker would fix the transpose but
    deadlocks the CPU collectives runtime on the interleaved-1F1B engine's
    cond/scan structure, so manual-AD engines (``parallel/pp.py``) require
    in-body row-parallel reductions to use THIS op. Under vma-on shard_map
    or outer-``jax.grad`` paths it is numerically identical to the raw
    psum's correct behavior, so the blocks use it unconditionally.
    """
    return lax.psum(x, axis)


def _psum_identity_fwd(x, axis: str):
    return lax.psum(x, axis), None


def _psum_identity_bwd(axis: str, _, g):
    # The primal input is VARYING over ``axis`` while the psum output (and
    # hence ``g``) is invariant — under vma-ON shard_map the bwd rule must
    # re-vary the cotangent to type-match the input (a no-op on values;
    # also a no-op under check_vma=False bodies like the interleaved
    # engine, where pcast is accepted and vma isn't tracked).
    return (lax.pcast(g, (axis,), to="varying"),)


psum_identity_bwd.defvjp(_psum_identity_fwd, _psum_identity_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def identity_fwd_psum_bwd(x, axis: str):
    """Identity whose TRANSPOSE is ``psum`` — Megatron's "copy to tensor-
    parallel region" marker (the conjugate of :func:`psum_identity_bwd`).

    Placed where a replicated activation FANS OUT into per-rank slices
    (before the column-parallel projections): in the backward pass every
    rank's parallel region contributes only its slice's share of the input
    cotangent, and this op's transpose sums them into the true full
    cotangent — on every rank, identically. Together the f/g pair makes a
    manually-differentiated region (``jax.vjp`` inside
    ``shard_map(check_vma=False)``, e.g. the interleaved-1F1B engine)
    produce correct per-rank gradients with no boundary fix-ups: sliced
    params get their owned-slice grads, replicated params get identical
    full grads.

    MANUAL-AD ONLY: under vma-ON shard_map with outer autodiff, jax's own
    invariant-input boundary already supplies the sum — inserting f there
    double-counts. The models gate it on ``manual_tp_ad`` accordingly;
    new call sites must do the same.
    """
    return x


def _identity_fwd(x, axis: str):
    return x, None


def _identity_bwd(axis: str, _, g):
    return (lax.psum(g, axis),)


identity_fwd_psum_bwd.defvjp(_identity_fwd, _identity_bwd)


