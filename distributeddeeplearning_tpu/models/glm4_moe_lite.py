"""GLM-4.7-Flash family (``glm4_moe_lite``): multi-head latent attention,
sigmoid-routed experts with a shared one, a multi-token-prediction module.

The DeepSeek-V3 block as GLM-4.7-Flash instantiates it
(huggingface.co/zai-org/GLM-4.7-Flash ``config.json``; equations and
departures in ``benchmarks/references/glm4_moe_lite.py``, the plain
reference the tests and the served cell hold this file to):

- **MLA** — queries through a normed rank-``q_rank`` bottleneck; keys and
  values from ONE normed latent of rank ``kv_rank`` a token plus one RoPE
  key head shared by all query heads. Training and the shape-only init run
  the expanded form through ``transformer.attention_core``; serving caches
  the latent alone (``transformer.latent_paged_attention``: expanded at
  bulk prefill, absorbed at decode).
- **Experts** — token-choice routing with NO capacity and no dropped token
  at any batch shape: sigmoid scores in float32, a bias that selects but
  does not weigh (``noaux_tc``), the chosen scores normalised and scaled;
  the (token, choice) pairs are sorted by expert and the experts run as a
  grouped product over the runs (``jax.lax.ragged_dot``, which the TPU
  compiler lowers to its own grouped-matmul kernel). No dense
  [tokens, experts, capacity] dispatch tensor (``parallel/ep.route_top_k``,
  which the capacity models in ``models/moe.py`` use and which is why those
  stay fenced from serving), and bulk prefill and one-token decode route a
  token alike. The layer can be told which experts it holds
  (``RoutedExperts.held``: the chip's share of a deployment that divides
  each layer's experts over chips, ``models/cohere2_moe.py``); here every
  expert lives on the chip that runs the layer. The exchange between the
  holders is not built, and the ``ep`` mesh axis is refused for this model
  by the Trainer.
- The selection bias is a buffer here as published (training would move it
  outside the gradient, which is not built: it trains as a parameter).

Parameters are created in ``param_dtype`` (matrices) — never whole in
float32 first; norm gains and the router's bias are float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import register
from ..sharding import constrain
from .llama import RMSNorm, apply_rope, rope_tables
from .transformer import attention_core, dense_init, latent_paged_attention

HIGHEST = jax.lax.Precision.HIGHEST


def _init(*axes):
    return nn.with_logical_partitioning(dense_init(0.02), axes)


class SwiGLU(nn.Module):
    """down(silu(gate(x)) * up(x)), bias-free (``llama.LlamaMlp``'s
    mathematics with the parameters in ``param_dtype``)."""

    hidden_dim: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(name, features, axes):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, kernel_init=_init(*axes),
                name=name,
            )

        h = nn.silu(dense("gate", self.hidden_dim, ("embed", "mlp"))(x)) * (
            dense("up", self.hidden_dim, ("embed", "mlp"))(x)
        )
        return dense("down", x.shape[-1], ("mlp", "embed"))(h)


def route(x, router, bias, top_k: int, scale: float):
    """Token-choice routing of ``x`` [T, D]: (chosen experts [T, k] int32,
    their weights [T, k] float32). Scores are sigmoids computed in float32
    on a float32 copy of the input at full matmul precision (the TPU's
    default would round the operands to bfloat16); ``bias`` (None: the
    model publishes none) selects and does not weigh; the weights sum to
    ``scale``."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    ))
    _, chosen = jax.lax.top_k(
        scores if bias is None else scores + bias, top_k
    )
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale


# (token, choice) pairs the grouped product takes at once: a longer call
# runs it a chunk of tokens at a time (routing is a token's own, so the
# chunks are exact), and no more than [pairs, max(D, F)] of sorted rows,
# gate, up and down products are alive together. GLM-4.7-Flash's largest
# bucket (4096 tokens x 4) is one chunk.
_PAIR_CHUNK = 16384


class RoutedExperts(nn.Module):
    """The expert FFN: ``sum_i w_i E_i(x) + E_shared(x)`` (module doc).

    ``held=(first, count)`` tells the layer which of the
    ``num_routed_experts`` published experts live here (expert
    parallelism's layer, run on one chip without its exchange; default:
    all). The router keeps its published width, ``route`` picks the top k
    over all of them and normalises over all k chosen; the expert leaves
    are ``[count, ...]``; a pair whose expert is held elsewhere adds
    nothing here: the held pairs are sorted first and the grouped product
    is given the held experts' counts alone, so the rows after them belong
    to no group. ``expert_load`` stays ``[published]``: the router's view,
    what the deployment's other chips would receive.

    ``num_shared`` shared experts are one SwiGLU of width ``num_shared x
    F``; ``shared_combine`` is ``"sum"`` or ``"average"`` (their mean)."""

    num_routed_experts: int
    expert_dim: int
    top_k: int
    routed_scale: float
    num_shared: int = 1
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False  # serving: count the tokens each expert gets
    held: tuple | None = None  # (first, count) of the experts held here
    shared_combine: str = "sum"
    selection_bias: bool = True  # a router_bias that selects (noaux_tc)

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        E, F, k = self.num_routed_experts, self.expert_dim, self.top_k
        first, count = self.held or (0, E)
        if first < 0 or count < 1 or first + count > E:
            raise ValueError(f"held={self.held!r} of {E} experts")
        if self.shared_combine not in ("sum", "average"):
            raise ValueError(f"shared_combine={self.shared_combine!r}")
        partial = count < E
        router = self.param(
            "router", _init("embed", None), (D, E), self.param_dtype
        )
        bias = self.param(
            "router_bias",
            nn.with_logical_partitioning(nn.initializers.zeros, (None,)),
            (E,), jnp.float32,
        ) if self.selection_bias else None
        w_gate, w_up, w_down = (
            self.param(name, _init("expert", *axes), (count, *shape),
                       self.param_dtype)
            for name, axes, shape in (
                ("experts_gate", ("embed", "mlp"), (D, F)),
                ("experts_up", ("embed", "mlp"), (D, F)),
                ("experts_down", ("mlp", "embed"), (F, D)),
            )
        )
        xf = x.reshape(B * L, D)

        def sort_pairs(chosen):
            """(sorted order of the [T*k] pairs, the held experts' counts
            [count]): by expert, held pairs first (pair p = token p // k)."""
            flat = chosen.reshape(-1)
            if partial:
                local = flat - first
                flat = jnp.where((local >= 0) & (local < count), local, count)
            return (
                jnp.argsort(flat, stable=True),
                jnp.bincount(flat, length=count).astype(jnp.int32),
            )

        def experts(xc, chosen, w, order, counts):
            xs = xc[order // k].astype(self.dtype)  # sorted by expert
            grouped = lambda a, b: jax.lax.ragged_dot(  # noqa: E731
                a, b.astype(self.dtype), counts
            )
            h = nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
            ys = grouped(h, w_down)  # [T*k, D], sorted by expert
            # Back to (token, choice) order, then the weighted sum over a
            # token's choices in float32.
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.size, dtype=order.dtype)
            )
            y = ys[back].reshape(-1, k, D)
            if partial:
                # Rows past the held groups are no group's: whatever the
                # kernel left there is not a number of this layer's.
                here = (chosen >= first) & (chosen < first + count)
                y = jnp.where(here[..., None], y, 0)
            return jnp.einsum(
                "tkd,tk->td", y.astype(jnp.float32), w
            ).astype(self.dtype)

        with jax.named_scope("moe_route"):
            chosen, w = route(xf, router, bias, k, self.routed_scale)
            chunks = -(-B * L * k // _PAIR_CHUNK)
            while (B * L) % chunks:  # whole chunks of tokens
                chunks += 1
            if chunks == 1:
                order, counts = sort_pairs(chosen)
            if self.decode:
                # Tokens routed to each expert by this call, every row of
                # the grouped product counted (idle lanes and prompt
                # padding are rows the experts compute too): a served
                # program hands it back with the step's tokens
                # (docs/OBSERVABILITY.md, moe_tokens_per_expert).
                self.variable(
                    "cache", "expert_load", jnp.zeros, (E,), jnp.int32
                ).value = counts if chunks == 1 and not partial else (
                    jnp.bincount(chosen.reshape(-1), length=E).astype(
                        jnp.int32
                    )
                )
        with jax.named_scope("moe_experts"):
            if chunks == 1:
                y = experts(xf, chosen, w, order, counts)
            else:
                split = lambda a: a.reshape(  # noqa: E731
                    chunks, B * L // chunks, *a.shape[1:]
                )
                y = jax.lax.map(
                    lambda c: experts(*c, *sort_pairs(c[1])),
                    (split(xf), split(chosen), split(w)),
                ).reshape(B * L, D)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(
                F * self.num_shared, self.dtype, self.param_dtype,
                name="shared",
            )(x)
            if self.shared_combine == "average":
                shared = shared / self.num_shared
        return y.reshape(B, L, D) + shared


class MlaAttention(nn.Module):
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    rms_eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    attn_impl: str = "xla"
    mesh: object = None
    decode: bool = False
    kv_pages: tuple | None = None

    @nn.compact
    def __call__(self, x):
        B, L, E = x.shape
        H, dn, dr = self.num_heads, self.nope_dim, self.rope_dim
        if self.decode and self.kv_pages is None:
            raise NotImplementedError(
                "latent attention x contiguous decode cache (generate.py): "
                "only the paged serving cache holds the latent — serve "
                "through serving.ServingEngine (kv_pages)"
            )

        def dense(name, features, axes, **kw):
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, kernel_init=_init(*axes),
                name=name, **kw,
            )

        def norm(name):
            return RMSNorm(self.rms_eps, self.dtype, name=name)

        with jax.named_scope("mla_project"):
            c_q = norm("q_a_norm")(dense("q_a", self.q_rank, ("embed", None))(x))
            q = dense("q_b", (H, dn + dr), (None, "heads", "kv"))(c_q)
            kv = dense("kv_a", self.kv_rank + dr, ("embed", None))(x)
            c_kv = norm("kv_a_norm")(kv[..., : self.kv_rank])
            # [rank, H, nope | v]: a plain parameter, because the absorbed
            # form multiplies by its two halves directly.
            kv_b = self.param(
                "kv_b", _init(None, "heads", "kv"),
                (self.kv_rank, H, dn + self.v_dim), self.param_dtype,
            ).astype(self.dtype)
            positions = jnp.arange(L)
            lens_var = None
            if self.decode:
                # Per-row RoPE positions from the per-row cursor, read
                # before latent_paged_attention advances it.
                lens_var = self.variable(
                    "cache", "seq_lens", lambda: jnp.zeros((B,), jnp.int32)
                )
                if not self.is_initializing():
                    positions = lens_var.value[:, None] + positions[None, :]
            cos, sin = rope_tables(positions, dr, self.rope_theta)
            q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
            k_rope = apply_rope(
                kv[..., None, self.kv_rank:], cos, sin
            )[:, :, 0]  # one head, shared by all H
        if self.decode:
            out = latent_paged_attention(
                self, q_nope, q_rope, c_kv, k_rope, kv_b[..., :dn],
                kv_b[..., dn:], dtype=self.dtype, kv_pages=self.kv_pages,
                lens_var=lens_var,
            )
        else:
            with jax.named_scope("mla_attend"):
                kvx = jnp.einsum("blr,rhe->blhe", c_kv, kv_b)
                k = jnp.concatenate([
                    kvx[..., :dn],
                    jnp.broadcast_to(k_rope[:, :, None], (B, L, H, dr)),
                ], axis=-1)
                out = attention_core(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k,
                    kvx[..., dn:], impl=self.attn_impl, causal=True,
                    dtype=self.dtype, mesh=self.mesh,
                )
        return dense(
            "out", E, ("heads", "kv", "embed"), axis=(-2, -1)
        )(out)


@dataclasses.dataclass(frozen=True)
class Arch:
    """What a block needs of the model's fields (same names)."""

    num_heads: int
    embed_dim: int
    mlp_dim: int
    expert_dim: int
    num_routed_experts: int
    num_shared: int
    top_k: int
    routed_scale: float
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    rms_eps: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    attn_impl: str
    mesh: object
    decode: bool
    kv_pages: tuple | None


class Glm4Block(nn.Module):
    """``h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))``; the FFN is a
    dense SwiGLU (``dense=True``) or the routed experts."""

    cfg: Arch
    dense: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        x = x + MlaAttention(
            c.num_heads, c.q_rank, c.kv_rank, c.nope_dim, c.rope_dim,
            c.v_dim, c.rope_theta, c.rms_eps, c.dtype, c.param_dtype,
            attn_impl=c.attn_impl, mesh=c.mesh, decode=c.decode,
            kv_pages=c.kv_pages, name="attn",
        )(RMSNorm(c.rms_eps, c.dtype, name="attn_norm")(x))
        x = constrain(x, "batch", "seq", "embed")
        h = RMSNorm(c.rms_eps, c.dtype, name="mlp_norm")(x)
        if self.dense:
            x = x + SwiGLU(c.mlp_dim, c.dtype, c.param_dtype, name="mlp")(h)
        else:
            x = x + RoutedExperts(
                c.num_routed_experts, c.expert_dim, c.top_k, c.routed_scale,
                c.num_shared, c.dtype, c.param_dtype, c.decode, name="moe",
            )(h)
        return constrain(x, "batch", "seq", "embed")


class MtpModule(nn.Module):
    """Multi-token prediction (DeepSeek-V3 §2.2, depth 1): from the main
    model's hidden state at i and the embedding of token i+1, one more
    expert block scores token i+2."""

    cfg: Arch

    @nn.compact
    def __call__(self, hidden, next_emb):
        c = self.cfg
        x = jnp.concatenate([
            RMSNorm(c.rms_eps, c.dtype, name="enorm")(next_emb),
            RMSNorm(c.rms_eps, c.dtype, name="hnorm")(hidden),
        ], axis=-1)
        x = nn.Dense(
            c.embed_dim, use_bias=False, dtype=c.dtype,
            param_dtype=c.param_dtype, kernel_init=_init(None, "embed"),
            name="eh_proj",
        )(x)
        x = Glm4Block(c, dense=False, name="block")(x)
        return RMSNorm(c.rms_eps, c.dtype, name="norm")(x)


class Glm4MoeLite(nn.Module):
    vocab_size: int = 154880
    max_len: int = 202752
    num_layers: int = 47
    first_dense: int = 1  # leading dense layers (first_k_dense_replace)
    num_heads: int = 20
    embed_dim: int = 2048
    mlp_dim: int = 10240
    expert_dim: int = 1536
    num_routed_experts: int = 64
    num_shared: int = 1
    top_k: int = 4
    routed_scale: float = 1.8
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    # Multi-token-prediction modules (num_nextn_predict_layers): 0 or 1.
    # With one, ``mtp=True`` at call time also returns its logits.
    num_mtp: int = 0
    dtype: jnp.dtype = jnp.float32  # compute
    param_dtype: jnp.dtype = jnp.float32  # matrices as stored
    attn_impl: str = "xla"
    mesh: object = None
    chunked_head: bool = False
    decode: bool = False
    # Paged serving cache (serving/engine.py): the latent pool. The read
    # path and codec knobs exist because the engine sets them on every
    # model; only their defaults are built here (check_serving_composition
    # refuses the rest by name).
    kv_pages: tuple | None = None
    paged_kernel: str = "reference"
    kv_quant: str = "off"

    @nn.compact
    def __call__(self, tokens, train: bool = False, mtp: bool = False):
        B, L = tokens.shape
        if L > self.max_len:
            raise ValueError(f"seq_len {L} exceeds max_len {self.max_len}")
        if self.paged_kernel != "reference" or self.kv_quant != "off":
            raise NotImplementedError(
                f"latent paged cache x paged_kernel={self.paged_kernel!r} / "
                f"kv_quant={self.kv_quant!r}: only the gather read path on "
                "an unquantized latent pool is built"
            )
        if self.num_mtp not in (0, 1):
            raise NotImplementedError(
                f"num_mtp={self.num_mtp}: one prediction depth is built"
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
        for i in range(self.num_layers):
            x = Glm4Block(
                arch, dense=i < self.first_dense, name=f"block_{i}"
            )(x)
        head_ve = self.param(
            "lm_head", _init("embed", "vocab"),
            (self.embed_dim, self.vocab_size), self.param_dtype,
        ).astype(self.dtype).T

        def head(hidden):
            if self.chunked_head:
                from ..ops.chunked_xent import head_output

                return head_output(hidden, head_ve)
            return jnp.einsum(
                "ble,ve->blv", hidden, head_ve
            ).astype(jnp.float32)

        out = head(RMSNorm(self.rms_eps, self.dtype, name="norm")(x))
        if mtp and (self.decode or not self.num_mtp):
            raise NotImplementedError(
                f"mtp=True x decode={self.decode}, num_mtp={self.num_mtp}: "
                "the multi-token-prediction module runs on whole sequences "
                "of a model built with num_mtp=1 (it is not served: no "
                "self-drafting)"
            )
        if self.decode or not (mtp or (self.num_mtp and self.is_initializing())):
            return out
        # Row i: hidden state i with the embedding of token i+1.
        hidden = MtpModule(arch, name="mtp")(x[:, :-1], embed(tokens[:, 1:]))
        return (out, head(hidden)) if mtp else out


@register("glm4_moe_lite")
def glm4_moe_lite(size: str = "flash", **kwargs):
    sizes = {
        # GLM-4.7-Flash as published (the class defaults), and a test's size
        "flash": {},
        "tiny": dict(
            vocab_size=256, max_len=256, num_layers=3, num_heads=4,
            embed_dim=64, mlp_dim=160, expert_dim=48, num_routed_experts=8,
            top_k=2, q_rank=24, kv_rank=16, nope_dim=12, rope_dim=4,
            v_dim=16,
        ),
    }
    defaults = dict(sizes[size])
    defaults.update(kwargs)
    for key in ("dtype", "param_dtype"):
        if key in defaults:
            defaults[key] = jnp.dtype(defaults[key])
    return Glm4MoeLite(**defaults)
