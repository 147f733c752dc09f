"""Chunked tied-head softmax cross-entropy — the LM-head memory fix.

The reference computes its LM loss through a CUDA kernel over full logits
(``BASELINE.json:5`` "CUDA forward/backward kernels"); on GPU that is a
[B*L, V] matmul feeding a fused softmax-xent. On TPU the equivalent
materialization is the single largest tensor in the whole GPT-2 train step:
``[32, 1024, 50257]`` fp32 logits = **6.6 GB of HBM** — 40% of a v5e chip —
alive across the whole backward pass, for a loss that only ever reduces
them to one scalar per token.

TPU-native fix: never materialize the logits. ``lax.scan`` over chunks of
the sequence dimension computes each ``[B, Lc, V]`` logits block, reduces
it to per-token cross-entropy, and drops it; ``jax.checkpoint`` on the
chunk body makes the backward pass RECOMPUTE each block instead of saving
it. Peak head memory falls from ``L/Lc`` blocks to one (e.g. 6.6 GB →
0.8 GB at Lc=128) at the cost of one extra head matmul in the backward —
~15% more model FLOPs for GPT-2 124M, the classic remat trade
(SURVEY.md §1b "jax.checkpoint / rematerialisation").

Everything is plain XLA (einsum + scan), so it runs under any mesh: GSPMD
partitions each chunk's einsum exactly like the unchunked head (batch over
``dp/fsdp``, vocab over ``tp``), and the per-chunk softmax reductions ride
the same collectives.

Models opt in with ``chunked_head=True`` (``models/gpt2.py``,
``models/bert.py``), returning a :data:`ChunkedHeadOut` dict instead of
logits; the LM/MLM tasks (``train.py``) route it here. Parity with the
full-logits path is pinned to 1e-5 (loss AND grads) in
``tests/test_chunked_xent.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax

# Models with chunked_head=True return this dict shape instead of logits:
#   hidden [B, L, E] (final, post-LN/transform), emb [V, E] tied decoder,
#   bias [V] or None. A dict (not a custom pytree) keeps Trainer/jit
# plumbing completely unaware of the feature.
ChunkedHeadOut = dict


def head_output(hidden, emb, bias=None) -> ChunkedHeadOut:
    """What a ``chunked_head=True`` model returns."""
    out = {"hidden": hidden, "emb": emb}
    if bias is not None:
        out["bias"] = bias
    return out


def is_chunked_head(out) -> bool:
    return isinstance(out, dict) and "hidden" in out and "emb" in out


@jax.named_scope("chunked_xent")
def chunked_xent(
    out: ChunkedHeadOut,
    targets: jax.Array,
    *,
    seq_chunk: int = 128,
) -> jax.Array:
    """Per-token softmax cross-entropy [B, L] fp32 without full logits.

    ``targets`` is [B, L] int; positions are assumed in-vocab (same
    contract as the full-logits path). ``seq_chunk`` is the number of
    sequence positions whose logits are alive at once; L is padded up to a
    multiple (padded positions computed then dropped — cheaper than a mask
    inside the hot scan body).
    """
    hidden, emb = out["hidden"], out["emb"]
    bias = out.get("bias")
    B, L, E = hidden.shape
    seq_chunk = max(1, min(seq_chunk, L))
    n_chunks = -(-L // seq_chunk)
    pad = n_chunks * seq_chunk - L
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    # [n, B, Lc, ...]: scan over leading dim.
    h = hidden.reshape(B, n_chunks, seq_chunk, E).swapaxes(0, 1)
    t = targets.reshape(B, n_chunks, seq_chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, ht):
        hc, tc = ht
        # Same compute/dtype recipe as nn.Embed.attend + the fp32 cast the
        # tasks' _xent applies — parity with the unchunked path to 1e-6.
        logits = jnp.einsum("ble,ve->blv", hc, emb)
        if bias is not None:
            logits = logits + bias
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tc
        )
        return carry, per_tok

    _, per_tok = lax.scan(body, 0, (h, t))  # [n, B, Lc]
    per_tok = per_tok.swapaxes(0, 1).reshape(B, n_chunks * seq_chunk)
    return per_tok[:, :L]
