"""GPT-2 causal LM — reference workload 4 (``BASELINE.json:10``: "GPT-2 124M
LM (OpenWebText), ZeRO-1 optimizer-state sharding").

Faithful GPT-2 architecture (pre-LN, gelu_new/tanh, learned positions, tied
LM head, LN eps 1e-5) so golden tests can port weights from
``transformers.GPT2LMHeadModel`` and compare logits exactly. Default size is
the reference's 124M config (12L, 12H, 768d, vocab 50257).

This is also the long-context testbed: sequence activations are constrained
to the 'cp' axis. (An MoE variant swapping the MLP for expert-parallel
routing is planned alongside parallel/ep.py.)
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from . import register
from ..sharding import constrain
from .transformer import TransformerStack, layer_norm


class GPT2(nn.Module):
    vocab_size: int = 50257
    max_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    dropout_rate: float = 0.0
    remat: str = "none"
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"  # xla | ulysses | ulysses_flash | ring |
    # ring_pallas | flash (see models/transformer.py)
    mesh: object = None  # required for the ring attn_impl variants
    # True: skip the [B, L, V] logits materialization — return the final
    # hidden states + tied decoder for the tasks' chunked cross-entropy
    # (ops/chunked_xent.py; saves ~6.6 GB HBM at the 124m bench config).
    chunked_head: bool = False
    # KV-cache autoregressive decoding (generate.py): init with the full
    # generation budget to shape the caches, then feed one token per call.
    decode: bool = False
    # Paged serving cache (serving/engine.py): (num_blocks, block_size,
    # pages_per_seq) — per-row cursors, block-pool KV storage
    # (transformer.paged_decode_attention). Requires decode=True.
    kv_pages: tuple | None = None
    # Paged read path: 'reference' (gather) or 'pallas' (fused in-place
    # kernel, ops/paged_attention.py), as serving.engine.read_path chose.
    paged_kernel: str = "reference"
    # Paged pool storage: 'off' or 'int8' (quantize at scatter, dequant
    # on read) — serving.kv_quant (transformer.paged_decode_attention).
    kv_quant: str = "off"

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        B, L = tokens.shape
        if L > self.max_len:
            # XLA gather clamps OOB indices silently — fail loudly instead.
            raise ValueError(f"seq_len {L} exceeds max_len {self.max_len}")
        wte = nn.Embed(
            self.vocab_size,
            self.embed_dim,
            dtype=self.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="wte",
        )
        wpe = nn.Embed(
            self.max_len,
            self.embed_dim,
            dtype=self.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.01), ("pos", "embed")
            ),
            name="wpe",
        )
        if self.decode and self.kv_pages is not None:
            # Paged serving: per-ROW position cursors — rows decode at
            # different depths in one batch (continuous batching). The leaf
            # name 'seq_lens' matches the per-layer attention cursors so the
            # serving engine injects one [B] array everywhere by name.
            lens = self.variable(
                "cache", "seq_lens", lambda: jnp.zeros((B,), jnp.int32)
            )
            if self.is_initializing():
                positions = jnp.arange(L)[None, :]
            else:
                # Clamp: pad positions of a bucketed prefill may exceed
                # max_len - 1; their embeddings feed only discarded outputs.
                positions = jnp.minimum(
                    lens.value[:, None] + jnp.arange(L)[None, :],
                    self.max_len - 1,
                )
                lens.value = lens.value + L
        elif self.decode:
            # Position cursor for the cache-decoding path (the attention
            # cursors live per-layer; this one feeds wpe). 'start' ([B],
            # left-pad counts, default 0) keeps a left-padded row's first
            # real token at position 0 — HF's attention-mask-cumsum
            # position_ids numbering (see generate.py).
            pos = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = self.variable(
                "cache", "start", lambda: jnp.zeros((B,), jnp.int32)
            )
            if self.is_initializing():
                positions = jnp.arange(L)[None, :]
            else:
                positions = jnp.maximum(
                    pos.value + jnp.arange(L)[None, :]
                    - start.value[:, None],
                    0,
                )
                pos.value = pos.value + L
        else:
            positions = jnp.arange(L)[None, :]
        x = wte(tokens) + wpe(positions)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = constrain(x, "batch", "seq", "embed")
        x = TransformerStack(
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            head_dim=self.embed_dim // self.num_heads,
            mlp_dim=4 * self.embed_dim,
            pre_ln=True,
            causal=True,
            activation="gelu_tanh",
            ln_eps=1e-5,
            dropout_rate=self.dropout_rate,
            remat=self.remat,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            mesh=self.mesh,
            decode=self.decode,
            kv_pages=self.kv_pages,
            paged_kernel=self.paged_kernel,
            kv_quant=self.kv_quant,
            name="h",
        )(x, None, not train)
        x = layer_norm(1e-5, self.dtype, "ln_f")(x)
        if self.chunked_head:
            from ..ops.chunked_xent import head_output

            return head_output(x, jnp.asarray(wte.embedding, self.dtype))
        # Tied LM head (GPT-2 shares wte with the output projection).
        logits = wte.attend(x)
        return logits.astype(jnp.float32)


@register("gpt2")
def gpt2(size: str = "124m", **kwargs):
    sizes = {
        # (layers, heads, embed) — 124m is the reference workload's config.
        "tiny": (2, 4, 64),
        "124m": (12, 12, 768),
        "350m": (24, 16, 1024),
    }
    n_l, n_h, d = sizes[size]
    defaults = dict(num_layers=n_l, num_heads=n_h, embed_dim=d)
    defaults.update(kwargs)
    return GPT2(**defaults)
