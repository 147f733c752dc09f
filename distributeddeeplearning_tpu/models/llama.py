"""Llama-family decoder — RoPE, RMSNorm, SwiGLU, grouped-query attention.

Not a reference workload (``BASELINE.json:6-12`` stops at GPT-2-era
architectures); included because the framework's parallelism and kernel
layers should carry a modern decoder unchanged, and because GQA + RoPE is
the architecture the long-context machinery (ring attention over ``cp``)
actually gets used with in practice. Numerics are pinned against
``transformers.LlamaForCausalLM`` (weight-ported golden test, fp32).

TPU-first details, consistent with the rest of the zoo:
- projections carry the same logical axes as ``transformer.SelfAttention``
  (``('embed','heads','kv')``, MLP ``('embed','mlp')``), so Megatron TP is
  the same rules table — no new sharding code. GQA shards KV heads over
  ``tp`` too (an indivisible ``num_kv_heads % tp`` draws a loud
  RuntimeWarning from the ``sharding`` validator — XLA pads rather than
  fails, so it warns, not raises);
- RoPE tables are computed in fp32 and applied pre-repeat, so the KV cache
  dtype never touches position math;
- ``attn_impl`` ∈ {xla, flash, ring, ring_pallas}: the fused flash kernel
  and the ring context-parallel cores take the GQA-repeated q/k/v exactly
  like MHA — repeat-then-core is the standard GQA lowering;
- RMSNorm reduces in fp32 regardless of compute dtype;
- ``chunked_head=True`` returns hidden + the decoder matrix (the untied
  lm_head param, or the embedding table when ``tie_embeddings=True``) for
  the chunked cross-entropy (``ops/chunked_xent.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from . import register
from ..comms import identity_fwd_psum_bwd, psum_identity_bwd
from ..sharding import constrain
from .transformer import (
    attention_core,
    decode_attention,
    dense_init,
    paged_decode_attention,
)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
            (x.shape[-1],),
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (normed * scale.astype(jnp.float32)).astype(self.dtype)


def rope_tables(positions, head_dim: int, theta: float):
    """fp32 (cos, sin) tables, [..., L, head_dim//2] — HF Llama's layout
    (``inv_freq = theta ** -(arange(0, d, 2) / d)``). ``positions`` may be
    [L] (shared) or [B, L] (per-row, e.g. left-padded decode)."""
    half = head_dim // 2
    inv_freq = theta ** -(np.arange(0, half, dtype=np.float32) * 2 / head_dim)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin):
    """Rotate-half RoPE on [B, L, H, D] (HF formulation: the two halves of
    the head dim rotate against each other). Tables are [L, half] (shared
    positions) or [B, L, half] (per-row positions)."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    expand = (
        (lambda t: t[None, :, None, :]) if cos.ndim == 2
        else (lambda t: t[:, :, None, :])
    )
    c = expand(cos)
    s = expand(sin)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


class LlamaAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.float32
    # xla | flash | ring | ring_pallas | ulysses | ulysses_flash
    attn_impl: str = "xla"
    mesh: object = None  # required for the ring variants
    # Manual tensor parallelism (inside an explicit shard_map, e.g. PP×TP):
    # the module then sees tp-LOCAL head counts and psums the row-parallel
    # out-projection over this axis (projections are bias-free, so no
    # bias pre-scaling is needed — cf. transformer.SelfAttention).
    psum_axis: str | None = None
    manual_tp_ad: bool = False  # see transformer.SelfAttention.manual_tp_ad
    decode: bool = False  # KV-cache decoding (transformer.decode_attention)
    # Paged serving cache (transformer.paged_decode_attention): per-row
    # cursors + block-pool KV storage. Requires decode=True.
    kv_pages: tuple | None = None
    # Paged read path: 'reference' (gather) or 'pallas' (fused in-place
    # kernel, ops/paged_attention.py), as serving.engine.read_path chose.
    paged_kernel: str = "reference"
    # Paged pool storage: 'off' or 'int8' (quantize at scatter, dequant
    # on read) — serving.kv_quant (transformer.paged_decode_attention).
    kv_quant: str = "off"

    @nn.compact
    def __call__(self, x):
        B, L, E = x.shape
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} not a multiple of "
                f"num_kv_heads {self.num_kv_heads}"
            )
        if self.psum_axis is not None and self.manual_tp_ad:
            # Megatron f: entry of the tensor-parallel region (conjugate of
            # the psum_identity_bwd at its exit).
            x = identity_fwd_psum_bwd(x, self.psum_axis)

        def proj(name, heads):
            return nn.DenseGeneral(
                features=(heads, self.head_dim),
                use_bias=False,  # Llama projections are bias-free
                dtype=self.dtype,
                kernel_init=nn.with_logical_partitioning(
                    dense_init(0.02), ("embed", "heads", "kv")
                ),
                name=name,
            )

        q = proj("query", self.num_heads)(x)
        k = proj("key", self.num_kv_heads)(x)
        v = proj("value", self.num_kv_heads)(x)

        positions = jnp.arange(L)
        idx_var = None
        start_var = None
        lens_var = None
        if self.decode and self.kv_pages is not None:
            # Paged serving: per-ROW RoPE positions from the per-row cursor
            # (registered here so RoPE sees it BEFORE paged_decode_attention
            # advances it). Serving rows are never left-padded — no 'start'.
            lens_var = self.variable(
                "cache", "seq_lens", lambda: jnp.zeros((B,), jnp.int32)
            )
            if not self.is_initializing():
                positions = lens_var.value[:, None] + positions[None, :]
        elif self.decode:
            # RoPE at the cache cursor; the variables are registered ONCE
            # here and passed into decode_attention (which advances idx).
            idx_var = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            start_var = self.variable(
                "cache", "start", lambda: jnp.zeros((B,), jnp.int32)
            )
            if not self.is_initializing():
                # Per-row positions: a left-padded row's first REAL token
                # rotates at position 0 (HF computes position_ids from the
                # attention-mask cumsum — same contiguous numbering).
                positions = jnp.maximum(
                    idx_var.value + positions[None, :]
                    - start_var.value[:, None],
                    0,
                )
        cos, sin = rope_tables(positions, self.head_dim, self.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        # GQA: repeat KV groups up to the query head count, then run any
        # MHA core. HF orders repeats group-major (head g*r+i reads kv g).
        # Decode caches the PRE-repeat kv (num_kv_heads slabs — GQA's cache
        # memory benefit, ADVICE r3 #4) and repeats per step at use.
        rep = self.num_heads // self.num_kv_heads
        if self.decode and self.kv_pages is not None:
            if self.attn_impl != "xla":
                raise NotImplementedError(
                    "paged decode supports attn_impl='xla' only, got "
                    f"{self.attn_impl!r}"
                )
            out = paged_decode_attention(
                self, q, k, v, dtype=self.dtype, kv_pages=self.kv_pages,
                num_rep=rep, lens_var=lens_var, kernel=self.paged_kernel,
                kv_quant=self.kv_quant,
            )
        elif self.decode:
            out = decode_attention(
                self, q, k, v, dtype=self.dtype, attn_impl=self.attn_impl,
                idx_var=idx_var, num_rep=rep, start_var=start_var,
            )
        else:
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            if self.attn_impl in ("ulysses", "ulysses_flash"):
                # Sequence<->heads all-to-all reshard around an MHA core
                # (GQA already repeated above, so head counts match q).
                from ..parallel.sp_ulysses import ulysses_attention

                out = ulysses_attention(
                    q, k, v, flash=self.attn_impl == "ulysses_flash",
                    causal=True, dtype=self.dtype, mesh=self.mesh,
                    num_heads=self.num_heads,
                )
            else:
                out = attention_core(
                    q, k, v, impl=self.attn_impl, causal=True,
                    dtype=self.dtype, mesh=self.mesh,
                )

        out = nn.DenseGeneral(
            features=E,
            axis=(-2, -1),
            use_bias=False,
            dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(0.02), ("heads", "kv", "embed")
            ),
            name="out",
        )(out)
        if self.psum_axis is not None:
            out = psum_identity_bwd(out, self.psum_axis)
        return out


class LlamaMlp(nn.Module):
    """SwiGLU: down(silu(gate(x)) * up(x)); column-parallel gate/up, row-
    parallel down — the same TP split as the GELU MLP."""

    hidden_dim: int
    dtype: jnp.dtype = jnp.float32
    psum_axis: str | None = None  # manual TP (see LlamaAttention)
    manual_tp_ad: bool = False  # see transformer.SelfAttention.manual_tp_ad

    @nn.compact
    def __call__(self, x):
        if self.psum_axis is not None and self.manual_tp_ad:
            # Megatron f (see LlamaAttention): entry of the parallel region.
            x = identity_fwd_psum_bwd(x, self.psum_axis)

        def col(name):
            return nn.Dense(
                self.hidden_dim, use_bias=False, dtype=self.dtype,
                kernel_init=nn.with_logical_partitioning(
                    dense_init(0.02), ("embed", "mlp")
                ),
                name=name,
            )

        h = nn.silu(col("gate")(x)) * col("up")(x)
        out = nn.Dense(
            x.shape[-1], use_bias=False, dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                dense_init(0.02), ("mlp", "embed")
            ),
            name="down",
        )(h)
        if self.psum_axis is not None:
            out = psum_identity_bwd(out, self.psum_axis)
        return out


def decoder_matrix(module, embed, *, tie: bool, embed_dim: int,
                   vocab_size: int, dtype, vocab_axis: str = "vocab"):
    """THE LM-head decoder resolver, [V, E]: the tied embedding table, or
    an untied ``lm_head`` param created on ``module``. One definition for
    Llama, LlamaMoe, and PipelinedLlama so the head cannot drift."""
    if tie:
        return jnp.asarray(embed.embedding, dtype)
    kernel = module.param(
        "lm_head",
        nn.with_logical_partitioning(dense_init(0.02), ("embed", vocab_axis)),
        (embed_dim, vocab_size),
    )
    return jnp.asarray(kernel, dtype).T


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_dim: int
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"
    mesh: object = None
    psum_axis: str | None = None  # manual TP inside shard_map (PP×TP)
    manual_tp_ad: bool = False  # see transformer.SelfAttention.manual_tp_ad
    # False inside pipeline stages: the body runs under shard_map on
    # per-device arrays, where global sharding constraints don't apply.
    constrain_out: bool = True
    decode: bool = False  # KV-cache decoding
    kv_pages: tuple | None = None  # paged serving cache (LlamaAttention)
    paged_kernel: str = "reference"  # paged read path (LlamaAttention)
    kv_quant: str = "off"  # paged pool storage codec (LlamaAttention)

    @nn.compact
    def __call__(self, x):
        x = x + LlamaAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            rope_theta=self.rope_theta, dtype=self.dtype,
            attn_impl=self.attn_impl, mesh=self.mesh,
            psum_axis=self.psum_axis, manual_tp_ad=self.manual_tp_ad,
            decode=self.decode, kv_pages=self.kv_pages,
            paged_kernel=self.paged_kernel, kv_quant=self.kv_quant,
            name="attn",
        )(RMSNorm(self.rms_eps, self.dtype, name="attn_norm")(x))
        if self.constrain_out:
            x = constrain(x, "batch", "seq", "embed")
        x = x + LlamaMlp(
            self.mlp_dim, self.dtype, psum_axis=self.psum_axis,
            manual_tp_ad=self.manual_tp_ad, name="mlp"
        )(RMSNorm(self.rms_eps, self.dtype, name="mlp_norm")(x))
        return constrain(x, "batch", "seq", "embed") if self.constrain_out else x


class Llama(nn.Module):
    vocab_size: int = 32000
    max_len: int = 4096
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int = 4
    embed_dim: int = 512
    mlp_dim: int = 1408
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    remat: str = "none"
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"
    mesh: object = None
    chunked_head: bool = False
    # KV-cache autoregressive decoding (generate.py): init with the full
    # generation budget to shape the caches, then feed one token per call.
    decode: bool = False
    # Paged serving cache (serving/engine.py): per-row cursors + block-pool
    # KV storage (transformer.paged_decode_attention). Requires decode=True.
    kv_pages: tuple | None = None
    # Paged read path: 'reference' (gather) or 'pallas' (fused in-place
    # kernel, ops/paged_attention.py), as serving.engine.read_path chose.
    paged_kernel: str = "reference"
    # Paged pool storage: 'off' or 'int8' — serving.kv_quant.
    kv_quant: str = "off"
    # True: the LM head shares the embedding table (Llama-3.2-class small
    # checkpoints; HF tie_word_embeddings) — no separate lm_head param.
    tie_embeddings: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        B, L = tokens.shape
        if L > self.max_len:
            raise ValueError(f"seq_len {L} exceeds max_len {self.max_len}")
        embed = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="embed",
        )
        x = embed(tokens)
        x = constrain(x, "batch", "seq", "embed")
        block = LlamaBlock
        if self.remat == "full":
            block = nn.remat(LlamaBlock)
        elif self.remat != "none":
            raise ValueError(f"unknown remat {self.remat!r}")
        for i in range(self.num_layers):
            x = block(
                self.num_heads, self.num_kv_heads,
                self.embed_dim // self.num_heads, self.mlp_dim,
                rope_theta=self.rope_theta, rms_eps=self.rms_eps,
                dtype=self.dtype, attn_impl=self.attn_impl, mesh=self.mesh,
                decode=self.decode, kv_pages=self.kv_pages,
                paged_kernel=self.paged_kernel, kv_quant=self.kv_quant,
                name=f"block_{i}",
            )(x)
        x = RMSNorm(self.rms_eps, self.dtype, name="norm")(x)
        decoder_ve = decoder_matrix(
            self, embed, tie=self.tie_embeddings,
            embed_dim=self.embed_dim, vocab_size=self.vocab_size,
            dtype=self.dtype,
        )
        if self.chunked_head:
            from ..ops.chunked_xent import head_output

            # chunked_xent wants the decoder as [V, E].
            return head_output(x, decoder_ve)
        return jnp.einsum("ble,ve->blv", x, decoder_ve).astype(jnp.float32)


@register("llama")
def llama(size: str = "tiny", **kwargs):
    sizes = {
        # (layers, heads, kv_heads, embed, mlp)
        "tiny": (2, 4, 2, 64, 128),
        "300m": (12, 16, 8, 1024, 2816),
        "1b": (16, 32, 8, 2048, 5632),
        "7b": (32, 32, 32, 4096, 11008),
    }
    n_l, n_h, n_kv, d, m = sizes[size]
    defaults = dict(
        num_layers=n_l, num_heads=n_h, num_kv_heads=n_kv,
        embed_dim=d, mlp_dim=m,
    )
    defaults.update(kwargs)
    return Llama(**defaults)
