"""Operations and bytes that the work needs, from shapes alone.

These count what the algorithm requires, not what an implementation does:
causal attention is counted once (the lower triangle), nothing recomputed
counts, and a decode step's bytes are the weights as stored plus the K and V
of the live tokens, whichever read path serves them. ``dims`` is a
configuration file's sizes: n_layer, n_head, n_embd, n_positions,
vocab_size.
"""

from __future__ import annotations


def gpt2_param_count(d: dict) -> int:
    L, D, V, P = d["n_layer"], d["n_embd"], d["vocab_size"], d["n_positions"]
    per_layer = 12 * D * D + 13 * D  # qkv, out, fc, proj + biases + 2 LN
    return V * D + P * D + L * per_layer + 2 * D


def gpt2_forward_flops(d: dict, n_tokens: int, ctx_sum: int,
                       n_head_rows: int) -> float:
    """Forward FLOPs of ``n_tokens`` positions through the blocks, where
    ``ctx_sum`` is the sum over those positions of the keys each attends to
    (its own included), plus the tied head at ``n_head_rows`` positions."""
    L, D, V = d["n_layer"], d["n_embd"], d["vocab_size"]
    dense = 24 * D * D * L * n_tokens  # qkv 6D^2, out 2D^2, mlp 16D^2
    attn = 4 * D * L * ctx_sum  # QK^T and PV: 2*D each per (query, key)
    head = 2 * D * V * n_head_rows
    return float(dense + attn + head)


def causal_ctx_sum(length: int, start: int = 0) -> int:
    """Keys seen by positions start..length-1 of one causal sequence."""
    return (length * (length + 1) - start * (start + 1)) // 2


def gpt2_train_flops_per_sample(d: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) of one ``seq_len`` sequence with a
    loss at every position: the MFU numerator, nothing recomputed."""
    return 3.0 * gpt2_forward_flops(
        d, seq_len, causal_ctx_sum(seq_len), seq_len
    )


def attention_train_work(d: dict, batch: int, seq_len: int) -> dict:
    """Least work of the attention cores of one training step: forward
    (QK^T, PV) and backward (dV, dP, dQ, dK), causal, all layers; bytes are
    q, k, v, o read or written once forward and q, k, v, o, do read and dq,
    dk, dv written once backward, in the 2-byte compute type."""
    L, D = d["n_layer"], d["n_embd"]
    pairs = batch * causal_ctx_sum(seq_len)
    flops = (2 + 4) * 2 * D * pairs * L
    nbytes = (4 + 8) * batch * seq_len * D * 2 * L
    return {"flops": float(flops), "bytes": float(nbytes)}


def gpt2_decode_bytes(d: dict, live_tokens: int, weight_bytes: int = 4,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once as stored (the
    position table's unused rows left out) and K and V of ``live_tokens``."""
    L, D, P = d["n_layer"], d["n_embd"], d["n_positions"]
    weights = (gpt2_param_count(d) - P * D) * weight_bytes
    kv = 2 * L * D * kv_bytes * live_tokens
    return float(weights + kv)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_c, t_m),
        "bound": "compute" if t_c >= t_m else "memory",
        "compute_s": t_c,
        "memory_s": t_m,
    }
