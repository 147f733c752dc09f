"""Command A+ family (``cohere2_moe``): window and NoPE-global attention
layers by a published list, grouped-query attention, sigmoid-routed experts
with averaged shared ones, a parallel block.

The block as ``CohereLabs/command-a-plus-05-2026`` instantiates it
(huggingface.co/CohereLabs/command-a-plus-05-2026 ``config.json``; equations
and departures in ``benchmarks/references/cohere2_moe.py``, the plain
reference the tests and the served cell hold this file to):

- **Parallel block** — ``h = LN(x); x' = x + Attn(h) + FFN(h)``: attention
  and the experts read the same normed input and both add to the residual.
  ``LN`` is a LayerNorm with a gain and no bias, statistics in float32.
- **Attention** — ``num_heads`` query heads on ``num_kv_heads`` key/value
  heads (query head i reads KV head ``i // (num_heads / num_kv_heads)``), no
  biases, no q/k norm. The kind of every layer comes from ONE list,
  ``layer_types``: a ``"sliding_attention"`` layer rotates q and k (RoPE
  over the whole head, pairs (2i, 2i + 1): ``rope_gptj``) and query t sees
  keys j with ``t - window < j <= t``; a ``"full_attention"`` layer has no
  positional encoding at all and the causal mask. Serving keeps two kinds
  of cached state side by side (``transformer.paged_decode_attention``:
  every token for a global layer, a window layer's window and no more);
  everywhere else the block runs ``transformer.attention_core`` under the
  band mask.
- **Experts** — ``glm4_moe_lite.RoutedExperts``: sigmoid scores in float32
  over all ``num_routed_experts`` published experts, the ``top_k`` largest
  normalised to 1, no selection bias and no routed scale;
  ``held_experts=(first, count)`` tells every layer which of them live here
  (the chip's share of a deployment that divides each layer's experts over
  chips: what the others hold adds nothing here and is not computed);
  ``num_shared`` shared experts whose mean is added.
- **Top** — ``logits = logit_scale * LN_f(x) E^T`` on the tied embedding.
  A sliced vocabulary is a smaller ``vocab_size``.

``max_len`` is the configuration's ``max_position_embeddings`` and costs
nothing: no position is learned. Training the family is not asked for and
not tested: the Trainer would build it (no ``num_experts`` field, so no
``ep`` axis; ``held_experts`` must then be all), without a load-balancing
loss and without the vision tower, which is left out everywhere.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import register
from ..sharding import constrain
from .glm4_moe_lite import RoutedExperts, _init
from .llama import rope_tables
from .transformer import attention_core, paged_decode_attention

LAYER_KINDS = ("sliding_attention", "full_attention")


def apply_rope_pairs(x, cos, sin):
    """RoPE on [B, L, H, D] with dimension 2i turning against 2i + 1
    (``rope_gptj``); tables [L, D/2] or [B, L, D/2] as ``rope_tables``
    gives them."""
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    expand = (
        (lambda t: t[None, :, None, :]) if cos.ndim == 2
        else (lambda t: t[:, :, None, :])
    )
    c, s = expand(cos), expand(sin)
    return jnp.stack(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).reshape(x.shape).astype(x.dtype)


def layer_norm(eps: float, dtype, name: str):
    return nn.LayerNorm(
        epsilon=eps, dtype=dtype, use_bias=False,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones, ("norm",)
        ),
        name=name,
    )


@dataclasses.dataclass(frozen=True)
class Arch:
    """What a block needs of the model's fields (same names)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    embed_dim: int
    expert_dim: int
    num_routed_experts: int
    held_experts: tuple | None
    num_shared: int
    shared_combine: str
    top_k: int
    window: int
    rope_theta: float
    ln_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    attn_impl: str
    mesh: object
    decode: bool
    kv_pages: tuple | None
    window_blocks: int
    paged_kernel: str
    kv_quant: str


class Cohere2Attention(nn.Module):
    cfg: Arch
    window: int | None  # None: a global layer (no positions, causal)

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, L, E = x.shape
        H, G, D = c.num_heads, c.num_kv_heads, c.head_dim
        if H % G:
            raise ValueError(f"{H} query heads on {G} KV heads")
        if c.decode and c.kv_pages is None:
            raise NotImplementedError(
                "window layers x contiguous decode cache (generate.py): "
                "only the paged serving cache keeps a window layer's window "
                "— serve through serving.ServingEngine (kv_pages)"
            )

        def proj(name, heads):
            return nn.DenseGeneral(
                (heads, D), use_bias=False, dtype=c.dtype,
                param_dtype=c.param_dtype,
                kernel_init=_init("embed", "heads", "kv"), name=name,
            )

        q, k, v = proj("q", H)(x), proj("k", G)(x), proj("v", G)(x)
        lens_var = None
        if c.decode:
            # Per-row positions from the per-row cursor, read before
            # paged_decode_attention advances it.
            lens_var = self.variable(
                "cache", "seq_lens", lambda: jnp.zeros((B,), jnp.int32)
            )
        if self.window is not None:
            positions = jnp.arange(L)
            if c.decode and not self.is_initializing():
                positions = lens_var.value[:, None] + positions[None, :]
            cos, sin = rope_tables(positions, D, c.rope_theta)
            q, k = apply_rope_pairs(q, cos, sin), apply_rope_pairs(k, cos, sin)
        if c.decode:
            out = paged_decode_attention(
                self, q, k, v, dtype=c.dtype, kv_pages=c.kv_pages,
                num_rep=H // G, lens_var=lens_var, kernel=c.paged_kernel,
                kv_quant=c.kv_quant, window=self.window,
                window_blocks=c.window_blocks,
            )
        else:
            band = None
            if self.window is not None:
                if c.attn_impl != "xla":
                    raise NotImplementedError(
                        f"window layer x attn_impl={c.attn_impl!r}: the "
                        "band mask runs on the xla core only "
                        "(ops/flash_attention.py has no window)"
                    )
                t = jnp.arange(L)
                band = (t[None, :] > t[:, None] - self.window)[None, None]
            scope = "attn_global" if self.window is None else "attn_window"
            with jax.named_scope(scope):
                rep = lambda a: jnp.repeat(a, H // G, axis=2)  # noqa: E731
                out = attention_core(
                    q, rep(k), rep(v), impl=c.attn_impl, causal=True,
                    dtype=c.dtype, mesh=c.mesh, mask=band,
                )
        return nn.DenseGeneral(
            E, use_bias=False, axis=(-2, -1), dtype=c.dtype,
            param_dtype=c.param_dtype,
            kernel_init=_init("heads", "kv", "embed"), name="out",
        )(out)


class Cohere2Block(nn.Module):
    """``h = LN(x); x + Attn(h) + FFN(h)`` (module docstring)."""

    cfg: Arch
    window: int | None

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = layer_norm(c.ln_eps, c.dtype, "norm")(x)
        attn = Cohere2Attention(c, self.window, name="attn")(h)
        ffn = RoutedExperts(
            c.num_routed_experts, c.expert_dim, c.top_k, 1.0, c.num_shared,
            c.dtype, c.param_dtype, c.decode, held=c.held_experts,
            shared_combine=c.shared_combine, selection_bias=False,
            name="moe",
        )(h)
        return constrain(x + attn + ffn, "batch", "seq", "embed")


class Cohere2Moe(nn.Module):
    vocab_size: int = 262144
    max_len: int = 200000
    layer_types: tuple = (
        ("sliding_attention",) * 3 + ("full_attention",)
    ) * 8
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    embed_dim: int = 4096
    expert_dim: int = 4096
    num_routed_experts: int = 128  # the router's width, as published
    held_experts: tuple | None = None  # (first, count) held here; None: all
    num_shared: int = 4
    shared_combine: str = "average"
    top_k: int = 8
    window: int = 4096
    rope_theta: float = 50000.0
    ln_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32  # compute
    param_dtype: jnp.dtype = jnp.float32  # matrices as stored
    attn_impl: str = "xla"
    mesh: object = None
    decode: bool = False
    # Paged serving cache (serving/engine.py): ``kv_pages`` sizes the
    # global layers' pool and every page table, ``window_blocks`` the
    # window layers' pool. The read path and codec knobs exist because the
    # engine sets them on every model; a window layer has only their
    # defaults (check_serving_composition refuses the rest by name).
    kv_pages: tuple | None = None
    window_blocks: int = 0
    paged_kernel: str = "reference"
    kv_quant: str = "off"

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        B, L = tokens.shape
        if L > self.max_len:
            raise ValueError(f"seq_len {L} exceeds max_len {self.max_len}")
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types must hold {LAYER_KINDS}, got {unknown or '()'}"
            )
        embed = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="embed",
        )
        arch = Arch(**{
            f.name: getattr(self, f.name) for f in dataclasses.fields(Arch)
        })
        x = constrain(embed(tokens), "batch", "seq", "embed")
        for i, kind in enumerate(self.layer_types):
            x = Cohere2Block(
                arch, self.window if kind == "sliding_attention" else None,
                name=f"block_{i}",
            )(x)
        x = layer_norm(self.ln_eps, self.dtype, "norm")(x)
        logits = jnp.einsum(
            "ble,ve->blv", x, embed.embedding.astype(self.dtype)
        ).astype(jnp.float32)
        return logits * self.logit_scale if self.logit_scale != 1 else logits


@register("cohere2_moe")
def cohere2_moe(size: str = "a_plus", **kwargs):
    sizes = {
        # Command A+ as published (the class defaults), and a test's size:
        # one period of the layer pattern, window 8
        "a_plus": {},
        "tiny": dict(
            layer_types=("sliding_attention",) * 3 + ("full_attention",),
            vocab_size=256, max_len=256, num_heads=8, num_kv_heads=2,
            head_dim=8, embed_dim=32, expert_dim=24, num_routed_experts=16,
            num_shared=2, top_k=4, window=8,
        ),
    }
    defaults = dict(sizes[size])
    defaults.update(kwargs)
    for key in ("dtype", "param_dtype"):
        if key in defaults:
            defaults[key] = jnp.dtype(defaults[key])
    for key in ("layer_types", "held_experts"):
        if defaults.get(key) is not None:
            defaults[key] = tuple(defaults[key])
    return Cohere2Moe(**defaults)
