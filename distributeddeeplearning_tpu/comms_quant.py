"""Compressed gradient collectives — EQuARX-style block-quantized all-reduce
and reduce-scatter (PAPERS.md: "EQuARX: Efficient Quantized AllReduce in
XLA", arXiv 2506.17615).

The gradient-sync all-reduce is the dominant inter-chip byte stream of a
data-parallel step (its time is not measured on the chip: no four-chip
cell, PERF.md §7). These wrappers cut those bytes ~4x by running the ring
algorithm on a **compressed payload**: every hop ships int8 values plus one
f32 scale per ``block_size`` elements (or a bf16 cast in ``bf16`` mode)
instead of f32, while accumulation stays in f32 on-device. Implemented with
``shard_map`` ring primitives (``lax.ppermute`` — one ICI-neighbor hop each),
so the compiled HLO's collective-permute payloads ARE the compressed bytes
and the byte count from the HLO (``utils/hlo.py``; asserted by
``tests/test_grad_comm.py``) shows the cut directly.

Quantization error discipline:

- **Block scales**: each ``block_size``-element block quantizes against its
  own max-abs, so one outlier only degrades its block (the EQuARX design
  point; default 256 keeps scale overhead at ~1.6%% of payload).
- **Error feedback** (:func:`ef_compress`): the caller threads a
  per-parameter residual (``TrainState.grad_residual``) through steps;
  each device compresses ``grad + residual`` and carries the compression
  error into the next step, so quantization error accumulates to zero mean
  instead of biasing convergence (EF-SGD semantics).
- **Hop-wise requantization** of partial sums inside the ring is NOT
  error-compensated — that residual lives on no single device. EQuARX
  measures this error as negligible at block granularity; the parity and
  convergence tests in ``tests/test_grad_comm.py`` bound it here.

All functions must be called INSIDE a ``shard_map`` body (they use
``lax.ppermute`` / ``lax.axis_index``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree


GRAD_COMM_MODES: tuple[str, ...] = ("fp32", "bf16", "int8")

DEFAULT_BLOCK_SIZE = 256


# ---------------------------------------------------------------------------
# Block-wise quantization
# ---------------------------------------------------------------------------


def block_quantize(x, block_size: int = DEFAULT_BLOCK_SIZE):
    """Quantize a flat f32 vector to (int8 values, one f32 scale per block).

    ``x.shape[0]`` must be a multiple of ``block_size`` (callers pad — see
    :func:`_pad_to`). The max-abs element of every block maps to exactly
    ±127, so ``scale = amax / 127`` and all-zero blocks keep scale 0 (their
    values quantize to 0 and dequantize to 0 without a divide-by-zero).
    """
    blocks = x.reshape(-1, block_size)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = (amax / 127.0).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(jnp.int8)
    return q, scale


def block_dequantize(q, scale):
    """Inverse of :func:`block_quantize` — flat f32 vector."""
    return (q.astype(jnp.float32) * scale).reshape(-1)


def _compress(x, mode: str, block_size: int):
    """Flat f32 -> compressed payload tuple (what actually rides the ring)."""
    if mode == "bf16":
        return (x.astype(jnp.bfloat16),)
    return block_quantize(x, block_size)


def _decompress(payload, mode: str):
    if mode == "bf16":
        return payload[0].astype(jnp.float32)
    return block_dequantize(*payload)


def compression_ratio(mode: str, block_size: int = DEFAULT_BLOCK_SIZE) -> float:
    """Payload bytes per f32 element (scales included), as a share of the
    f32 payload: what ``grad_sync_bytes`` and ``BucketLayout.wire_bytes``
    scale by."""
    if mode == "fp32":
        return 1.0
    if mode == "bf16":
        return 0.5
    return (1.0 + 4.0 / block_size) / 4.0  # int8 + f32 scale per block


def _pad_to(flat, multiple: int):
    pad = (-flat.shape[0]) % multiple
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


# ---------------------------------------------------------------------------
# Ring collectives on the compressed payload
# ---------------------------------------------------------------------------


def _ring_hop(payload, axis: str):
    """One neighbor hop: member i receives member i-1's payload tuple."""
    n = lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return tuple(lax.ppermute(p, axis, perm=perm) for p in payload)


def _ring_reduce_phase(flat, axis: str, mode: str, block_size: int):
    """Ring reduce-scatter pass over ``n`` equal chunks of ``flat``.

    Returns ``(partial, chunks, n, i)`` where ``partial`` is the fully
    reduced chunk with index ``(i + 1) % n`` held by member ``i`` (the
    standard ring layout after n-1 hops): at hop ``s`` member ``i`` ships
    its running partial compressed, receives the partial for chunk
    ``(i - 1 - s) % n``, decompresses, and adds its own slice of that chunk
    in f32.
    """
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    chunks = flat.reshape(n, -1)
    partial = lax.dynamic_slice_in_dim(chunks, i, 1, axis=0)[0]
    for s in range(n - 1):
        payload = _ring_hop(_compress(partial, mode, block_size), axis)
        received = _decompress(payload, mode)
        idx = (i - 1 - s) % n
        local = lax.dynamic_slice_in_dim(chunks, idx, 1, axis=0)[0]
        partial = received + local
    return partial, chunks, n, i


def quantized_all_reduce_flat(
    flat, axis: str, *, mode: str = "int8",
    block_size: int = DEFAULT_BLOCK_SIZE,
):
    """All-reduce-sum a flat f32 vector over ``axis``, shipping only
    compressed payloads (ring reduce-scatter + ring all-gather, both on
    int8+scales / bf16). ``flat.shape[0]`` must divide evenly into
    ``axis_size * block_size`` chunks — use :func:`_pad_to`.

    The result is bit-identical on every member: the gather phase
    distributes each reduced chunk in compressed form and every member —
    including the chunk's own reducer — uses the decompressed value.
    """
    n = lax.axis_size(axis)
    if n == 1 or mode == "fp32":
        return lax.psum(flat, axis)
    partial, _, n, i = _ring_reduce_phase(flat, axis, mode, block_size)
    # Gather phase: circulate the reduced chunks compressed. Every member
    # decompresses ITS OWN chunk too (not the f32 partial) so all members
    # reconstruct the same values.
    payload = _compress(partial, mode, block_size)
    out = jnp.zeros_like(partial.reshape(1, -1).repeat(n, 0))
    own_idx = (i + 1) % n
    out = lax.dynamic_update_slice_in_dim(
        out, _decompress(payload, mode)[None], own_idx, axis=0
    )
    for s in range(n - 1):
        payload = _ring_hop(payload, axis)
        # The payload received at hop s originated at member (i - 1 - s),
        # which holds reduced chunk (i - s) % n.
        idx = (i - s) % n
        out = lax.dynamic_update_slice_in_dim(
            out, _decompress(payload, mode)[None], idx, axis=0
        )
    return out.reshape(-1)


def quantized_reduce_scatter_flat(
    flat, axis: str, *, mode: str = "int8",
    block_size: int = DEFAULT_BLOCK_SIZE,
):
    """``lax.psum_scatter`` semantics (member ``i`` gets chunk ``i`` of the
    sum, tiled) on compressed payloads. One extra compressed hop moves the
    ring-final chunk ``(i+1) % n`` from its reducer to its owner."""
    n = lax.axis_size(axis)
    if n == 1 or mode == "fp32":
        return lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)
    partial, _, n, _ = _ring_reduce_phase(flat, axis, mode, block_size)
    payload = _ring_hop(_compress(partial, mode, block_size), axis)
    return _decompress(payload, mode)


# ---------------------------------------------------------------------------
# Error feedback + pytree gradient sync (what the Trainer calls)
# ---------------------------------------------------------------------------


def ef_compress(grads, residual, *, mode: str, block_size: int):
    """EF-SGD compression step on a gradient pytree.

    Compresses ``grads + residual`` once per device and returns
    ``(decompressed, new_residual)`` — ``new_residual`` is exactly the
    compression error, to be carried into the next step. The decompressed
    tree is what enters the ring: because every value already sits on its
    block's quantization grid (block boundaries are preserved downstream),
    the ring's first-hop quantization of it is lossless, so the residual
    captures the full send-side error.

    ``residual=None`` means EF off: grads pass through, residual stays None.
    """
    if residual is None or mode == "fp32":
        return grads, residual
    flat, unravel = ravel_pytree(grads)
    flat = flat.astype(jnp.float32)
    res_flat, _ = ravel_pytree(residual)
    total = flat + res_flat
    padded = _pad_to(total, block_size)
    sent = _decompress(
        _compress(padded, mode, block_size), mode
    )[: flat.shape[0]]
    return unravel(sent), unravel(total - sent)


def quantized_tree_all_reduce(
    grads, axis: str, *, mode: str = "int8",
    block_size: int = DEFAULT_BLOCK_SIZE, residual=None,
):
    """Gradient-sync entry point: all-reduce-sum a gradient pytree over
    ``axis`` on compressed payloads, with optional error feedback.

    The tree is raveled into ONE flat f32 buffer so the whole sync is a
    single fused ring (one compressed payload per hop, not one per
    parameter), then unraveled back. Returns ``(summed_grads,
    new_residual)``; divide by ``axis_size`` for the mean. Call inside
    ``shard_map``.
    """
    if mode not in GRAD_COMM_MODES:
        raise ValueError(
            f"grad_comm mode {mode!r} not in {GRAD_COMM_MODES}"
        )
    grads, new_residual = ef_compress(
        grads, residual, mode=mode, block_size=block_size
    )
    flat, unravel = ravel_pytree(grads)
    flat = flat.astype(jnp.float32)
    m = flat.shape[0]
    n = lax.axis_size(axis)
    padded = _pad_to(flat, n * block_size)
    summed = quantized_all_reduce_flat(
        padded, axis, mode=mode, block_size=block_size
    )
    return unravel(summed[:m]), new_residual


def zeros_residual(params, dtype=jnp.float32):
    """Per-parameter EF residual tree of zeros, shaped like ``params``.

    Each device carries its OWN residual (its local compression error), so
    the Trainer stores these leaves with a leading device dimension sharded
    over the data-parallel axis (see ``parallel/zero.residual_shardings``)
    and hands this per-device view into the shard_map body.
    """
    return jax.tree.map(
        lambda p: jnp.zeros(jnp.shape(p), dtype), params
    )


@functools.lru_cache(maxsize=None)
def _mode_doc(mode: str) -> str:
    return {
        "fp32": "uncompressed lax collectives",
        "bf16": "bf16-cast ring (2x byte reduction)",
        "int8": "block-quantized int8 ring (~4x byte reduction)",
    }[mode]
