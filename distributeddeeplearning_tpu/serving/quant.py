"""int8 weight-quantized serving.

Reuses the training stack's block quantizer (``comms_quant.block_quantize``:
int8 values + one f32 scale per block, max-abs → ±127) so serving and
gradient compression share one numeric format and one tested codec. Weights
are quantized ONCE at engine build (host side), stored as
``{"q": int8[?], "scale": f32[?, 1], "shape": ..., "size": ...}`` leaves —
~4x less HBM for the parameters — and dequantized on-use at graph entry: the
first op of every compiled prefill/decode graph rebuilds f32 weights, so the
matmuls themselves are unchanged. On TPU the dequant is fused into the
consumer's HBM→VMEM pipeline; the win is the 4x smaller resident footprint
(more KV blocks per chip), not FLOPs.

Only float leaves with ``ndim >= 2`` are quantized (embeddings, projections,
MLP kernels). Biases, layer-norm scales, and anything smaller than one
quant block stay f32 — they are a rounding error of the footprint and
disproportionately sensitive to rounding.

Each quantized leaf becomes a :class:`QuantizedLeaf` — a registered pytree
node whose CHILDREN are the (q, scale) arrays and whose aux data is the
static (shape, size, dtype) needed to rebuild, so the quantized tree is a
valid jit/AOT argument and the executable's signature carries int8 inputs.

Composition fence: quantized serving is validated for the dense decode
models (gpt2, llama). MoE router logits are fenced at config time
(``engine.check_serving_composition``) until calibrated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..comms_quant import block_dequantize, block_quantize, _pad_to


@jax.tree_util.register_pytree_node_class
class QuantizedLeaf:
    """One block-quantized parameter: arrays as pytree children, the
    reconstruction metadata as static aux data."""

    def __init__(self, q, scale, shape, size, dtype):
        self.q = q
        self.scale = scale
        self.shape = tuple(shape)
        self.size = int(size)
        self.dtype = str(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, self.size, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def __repr__(self):
        return f"QuantizedLeaf(shape={self.shape}, dtype={self.dtype})"


def _should_quantize(leaf, block_size: int) -> bool:
    return (
        hasattr(leaf, "ndim")
        and leaf.ndim >= 2
        and jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.size >= block_size
    )


def quantize_params(params, block_size: int = 256):
    """Quantize eligible param leaves to int8 blocks (host-side, once).

    Returns (tree, report): ``tree`` mirrors ``params`` with quantized
    leaves replaced by payload dicts, ``report`` has byte counts for the
    engine's ``stats()``.
    """
    orig_bytes = quant_bytes = 0

    def enc(leaf):
        nonlocal orig_bytes, quant_bytes
        orig_bytes += leaf.size * leaf.dtype.itemsize
        if not _should_quantize(leaf, block_size):
            quant_bytes += leaf.size * leaf.dtype.itemsize
            return leaf
        flat = _pad_to(jnp.ravel(leaf).astype(jnp.float32), block_size)
        q, scale = block_quantize(flat, block_size)
        q, scale = jax.device_get(q), jax.device_get(scale)
        quant_bytes += q.nbytes + scale.nbytes
        return QuantizedLeaf(q, scale, leaf.shape, leaf.size, leaf.dtype)

    tree = jax.tree_util.tree_map(enc, params)
    report = {
        "block_size": block_size,
        "param_bytes_fp": int(orig_bytes),
        "param_bytes_quant": int(quant_bytes),
        "ratio": round(quant_bytes / max(orig_bytes, 1), 4),
    }
    return tree, report


def dequantize_params(tree):
    """Rebuild the float param tree from :func:`quantize_params` output.

    Traceable — called INSIDE the compiled graphs so XLA sees int8 inputs
    and materializes the float weights on the fly.
    """

    def dec(node):
        if not isinstance(node, QuantizedLeaf):
            return node
        flat = block_dequantize(node.q, node.scale)
        return flat[: node.size].reshape(node.shape).astype(
            jnp.dtype(node.dtype)
        )

    return jax.tree_util.tree_map(
        dec, tree, is_leaf=lambda n: isinstance(n, QuantizedLeaf)
    )


def quantization_error(params, block_size: int = 256) -> float:
    """Max relative L2 round-trip error across quantized leaves (host-side
    sanity metric in the engine's ``quant_report``)."""
    worst = 0.0
    for leaf in jax.tree_util.tree_leaves(params):
        if not _should_quantize(leaf, block_size):
            continue
        flat = _pad_to(jnp.ravel(leaf).astype(jnp.float32), block_size)
        rt = block_dequantize(*block_quantize(flat, block_size))
        num = float(jnp.linalg.norm(rt - flat))
        den = float(jnp.linalg.norm(flat))
        if den > 0:
            worst = max(worst, num / den)
    return float(np.round(worst, 6))
