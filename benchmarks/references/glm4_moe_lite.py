"""Plain GLM-4.7-Flash (``glm4_moe_lite``): the forward pass in
straightforward ``jax.numpy`` and float32 — the reference the served cell
is held to.

It follows the published configuration
(huggingface.co/zai-org/GLM-4.7-Flash ``config.json``) and the DeepSeek-V3
description it is an instance of: RMSNorm pre-norm blocks; multi-head latent
attention in its **expanded** form (queries through a rank-``q_lora_rank``
bottleneck with a norm, keys and values expanded per head from one normed
latent of rank ``kv_lora_rank``, one RoPE key head shared by all query
heads); a dense SwiGLU in the leading ``first_k_dense_replace`` layers and,
after them, ``n_routed_experts`` SwiGLU experts chosen per token by sigmoid
scores (``topk_method=noaux_tc``: a bias selects, the unbiased scores weigh,
normalised over the chosen ones and scaled by ``routed_scaling_factor``)
plus one shared expert that every token takes; a final RMSNorm and an untied
head. No cache, no batching, no kernels. It imports nothing of the program:
its weights come from the seed, and the harness gives the same weights to
the program by renaming them (``program_tree``).

Departures and assumptions, each on purpose (the configuration file lists
them under ``assumed``):

- The checkpoint is the seed's weights **rounded to bfloat16 once** (the
  published checkpoint's dtype); the reference computes in float32 on those
  same values. Matrices are normal with std 0.02, norm gains 1 + 0.02 n,
  the router's selection bias 0.02 n (kept float32, as published; of
  the size of the gaps between neighbouring scores, so that it decides
  some choices and fixes none).
- RoPE pairs dimension i with i + 32 (rotate-half). The published code
  pairs 2i with 2i + 1; with seeded weights that is a fixed permutation of
  columns of ``q_b`` and ``kv_a``.
- The multi-token-prediction module (``num_nextn_predict_layers``) shares
  the embedding and the head with the main model and takes the last block's
  output **before** the final norm.
- Every expert is evaluated on every token and weighted by its routing
  weight, which is exactly 0 for a token not routed to it: the same numbers
  as running each expert on its own tokens, in one shape.
- ``precision`` selects the arithmetic of every matrix product but the
  router's, which is float32 always (the configuration states it so):
  "f32" (``Precision.HIGHEST``: the reference), "bf16" and "fp8" (operands
  rounded to float8_e4m3 after a per-tensor power-of-two scale, float32
  accumulation): the control of a bfloat16 configuration.

Positions the stated precision leaves open. Top-k routing is a step
function: where a token's last chosen expert leads the best one left out by
less than the rounding of the stated precision moves the scores, the program
(bfloat16 inputs to a float32 router) and this reference (float32
throughout) choose different experts, and the token's logits then differ by
as much as the control's do — and later layers' routing follows. On the chip
at the published widths the scores of a bfloat16 pass lie 0.0010 (first
expert layer) to 0.0017 (fifth) from the float32 ones, 2.5-3.7% of the
tokens change an expert in each layer, and 13% of the rows end up as far
from the float32 logits as an fp8 pass's typical row (PERF.md §6, PR 26).
No limit on a widest gap separates that from the control: over 12 sound
runs the widest gap of a served token read 0.91-2.02, the control's
1.2-1.5. So ``sequence_readout`` takes at every position the least margin,
over the expert layers, by which its float32 routing is decided
(``routing_margin``), and holds the two kinds of position to two measures
through the one number it returns (``best - picked``, which the harness
compares with the cell's limit):

- a position decided by more than ``ROUTING_MARGIN`` (0.006: a fifth of
  them; no expert changed at such a margin in 12 runs, the widest gap there
  was 0.041 where margins down to 0.004 let 0.35 through) is compared as it
  stands;
- at any other position the served token may lie below the best by what a
  change of routing moves a logit: ``OPEN_ALLOWANCE`` (3.0; the widest seen
  was 2.02) is taken off its gap, and what is left is compared. A token
  that is simply wrong lies 3.2-6.5 below (the planted ``altered_token``).

The margin is the reference's own (nothing of the program's routing
enters), no expert is left out, and nothing is skipped; what is given up is
a fault that moves only undecided positions, and by less than the allowance.

Memory: float32 copies of all the weights would be 15.6 GB at the cell's
size. ``make_weights`` therefore returns a handle (the key), and
``sequence_readout`` makes one layer's weights from the key at a time;
attention runs in blocks of queries.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256  # queries attended at once
# "Positions the stated precision leaves open" (module docstring; PERF.md
# §6, PR 26 has the readings both were set from): the least routing margin
# at which a position is held to the limit as it stands, and what a served
# token may lie below the best by at the others.
ROUTING_MARGIN = 0.006
OPEN_ALLOWANCE = 3.0
# sequence_readout pads a long sequence to a multiple of this: three shapes
# (a program each for the dense and the expert kind of layer, the embedding
# and the head) cover every length up to 6144.
READOUT_PAD = 2048

_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "num_nextn_predict_layers", "vocab_size",
)


def dims(cfg: dict) -> dict:
    d = {k: int(cfg[k]) for k in _KEYS}
    d["routed_scaling_factor"] = float(cfg["routed_scaling_factor"])
    d["rope_theta"] = float(cfg["rope_theta"])
    d["eps"] = float(cfg["rms_norm_eps"])
    d["n_positions"] = int(cfg["max_position_embeddings"])
    d["torch_dtype"] = str(cfg.get("torch_dtype", "bfloat16"))
    if not cfg.get("norm_topk_prob", True) or cfg.get("n_group", 1) != 1:
        raise ValueError("only norm_topk_prob=true and one group are built")
    return d


def seed_key(seed: int):
    """``--seed`` is any whole number up to a little over 2**31."""
    return jax.random.PRNGKey(int(seed))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _attn_shapes(d: dict) -> dict:
    D, H = d["hidden_size"], d["num_attention_heads"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    return {
        "attn_norm": (D,), "q_a": (D, d["q_lora_rank"]),
        "q_a_norm": (d["q_lora_rank"],),
        "q_b": (d["q_lora_rank"], H * qk),
        "kv_a": (D, d["kv_lora_rank"] + d["qk_rope_head_dim"]),
        "kv_a_norm": (d["kv_lora_rank"],),
        "kv_b": (d["kv_lora_rank"],
                 H * (d["qk_nope_head_dim"] + d["v_head_dim"])),
        "o": (H * d["v_head_dim"], D), "mlp_norm": (D,),
    }


def layer_shapes(d: dict, dense: bool) -> dict:
    """{leaf: shape} of one block, sorted by name where keys are drawn."""
    D = d["hidden_size"]
    out = _attn_shapes(d)
    if dense:
        F = d["intermediate_size"]
        out.update(gate=(D, F), up=(D, F), down=(F, D))
        return out
    E, F = d["n_routed_experts"], d["moe_intermediate_size"]
    Fs = F * d["n_shared_experts"]
    out.update(
        router=(D, E), router_bias=(E,),
        e_gate=(E, D, F), e_up=(E, D, F), e_down=(E, F, D),
        s_gate=(D, Fs), s_up=(D, Fs), s_down=(Fs, D),
    )
    return out


def _draw(key, name: str, shape) -> jax.Array:
    """One leaf from its key: the checkpoint's value, float32 holding a
    bfloat16-representable number (the router's bias: float32 as drawn)."""
    x = jax.random.normal(key, shape, F32)
    if name == "router_bias":
        return 0.02 * x
    x = 0.02 * x + (1.0 if name.endswith("norm") else 0.0)
    # Not ``.astype(bfloat16).astype(float32)``: under jit the TPU's
    # compiler drops that round trip as excess precision it may keep
    # (seen on the chip, PR 26), and the reference would then hold other
    # weights than the program's.
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _leaves(key, shapes: dict) -> dict:
    return {
        name: _draw(jax.random.fold_in(key, i), name, shape)
        for i, (name, shape) in enumerate(sorted(shapes.items()))
    }


def is_dense(i: int, d: dict) -> bool:
    return i < d["first_k_dense_replace"]


def layer_weights(key, i, d: dict, dense: bool) -> dict:
    """Block ``i``'s weights from the run's key (pure: usable under jit,
    where ``i`` may be traced: one program serves every layer of a kind)."""
    return _leaves(jax.random.fold_in(key, 1 + i), layer_shapes(d, dense))


def top_weights(key, d: dict) -> dict:
    D, V = d["hidden_size"], d["vocab_size"]
    return _leaves(
        jax.random.fold_in(key, 0),
        {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V)},
    )


def mtp_weights(key, d: dict) -> dict:
    """The multi-token-prediction module: two norms, the 2D -> D
    projection, one expert block and its final norm."""
    D = d["hidden_size"]
    k = jax.random.fold_in(key, 1 << 20)
    out = _leaves(k, {"enorm": (D,), "hnorm": (D,), "eh_proj": (2 * D, D),
                      "final_norm": (D,)})
    out["block"] = _leaves(jax.random.fold_in(k, 1), layer_shapes(d, False))
    return out


def weights_from_key(key, d: dict) -> dict:
    """Every weight as a pure function of a PRNG key (for use under jit,
    where each leaf is drawn, rounded and handed on without a float32 copy
    of the whole): ``top``, ``layers`` (a list) and ``mtp`` if counted."""
    w = {
        "top": top_weights(key, d),
        "layers": [
            layer_weights(key, i, d, is_dense(i, d))
            for i in range(d["num_hidden_layers"])
        ],
    }
    if d["num_nextn_predict_layers"]:
        w["mtp"] = mtp_weights(key, d)
    return w


def make_weights(seed: int, d: dict) -> dict:
    """A handle, not the weights: ``sequence_readout`` draws one layer at a
    time from it (float32 copies of all of them do not fit a chip)."""
    return {"key": seed_key(seed)}


# -- the program's names ----------------------------------------------------


def _block_tree(lw: dict, d: dict, dense: bool, dt) -> dict:
    H = d["num_attention_heads"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    kv = d["qk_nope_head_dim"] + d["v_head_dim"]
    m = lambda x: x.astype(dt)  # noqa: E731  (matrices: the stated dtype)
    swiglu = lambda p: {  # noqa: E731
        n: {"kernel": m(lw[p + n])} for n in ("gate", "up", "down")
    }
    out = {
        "attn_norm": {"scale": lw["attn_norm"]},
        "mlp_norm": {"scale": lw["mlp_norm"]},
        "attn": {
            "q_a": {"kernel": m(lw["q_a"])},
            "q_a_norm": {"scale": lw["q_a_norm"]},
            "q_b": {"kernel": m(lw["q_b"]).reshape(-1, H, qk)},
            "kv_a": {"kernel": m(lw["kv_a"])},
            "kv_a_norm": {"scale": lw["kv_a_norm"]},
            "kv_b": m(lw["kv_b"]).reshape(-1, H, kv),
            "out": {"kernel": m(lw["o"]).reshape(H, d["v_head_dim"], -1)},
        },
    }
    if dense:
        out["mlp"] = swiglu("")
    else:
        out["moe"] = {
            "router": m(lw["router"]), "router_bias": lw["router_bias"],
            "experts_gate": m(lw["e_gate"]), "experts_up": m(lw["e_up"]),
            "experts_down": m(lw["e_down"]), "shared": swiglu("s_"),
        }
    return out


def program_tree(w: dict, d: dict) -> dict:
    """The same weights under the names, shapes and dtypes of the
    program's flax tree (``models/glm4_moe_lite.py``): matrices in the
    checkpoint's dtype, gains and the router's bias float32."""
    dt = jnp.dtype(d["torch_dtype"])
    t = w["top"]
    out = {
        "embed": {"embedding": t["embed"].astype(dt)},
        "norm": {"scale": t["final_norm"]},
        "lm_head": t["lm_head"].astype(dt),
    }
    for i, lw in enumerate(w["layers"]):
        out[f"block_{i}"] = _block_tree(lw, d, is_dense(i, d), dt)
    if "mtp" in w:
        mw = w["mtp"]
        out["mtp"] = {
            "enorm": {"scale": mw["enorm"]}, "hnorm": {"scale": mw["hnorm"]},
            "eh_proj": {"kernel": mw["eh_proj"].astype(dt)},
            "block": _block_tree(mw["block"], d, False, dt),
            "norm": {"scale": mw["final_norm"]},
        }
    return out


def leaf_names_of_program_tree(tree: dict) -> dict:
    """{dotted leaf name: value} of a tree shaped like ``program_tree``'s."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(str(p.key) for p in path): v for path, v in flat}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _fp8_round(x):
    """Round to float8_e4m3 after a per-tensor power-of-two scale that
    puts the largest entry under the type's largest value (448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / 448.0)))
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(precision: str):
    """einsum at the named arithmetic (see the module docstring)."""
    if precision == "f32":
        return functools.partial(
            jnp.einsum, precision=HIGHEST, preferred_element_type=F32
        )
    if precision not in ("bf16", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    # 8-bit values are exact in bfloat16 and their products in the float32
    # accumulator, so one bfloat16 pass computes the fp8 product exactly.
    rnd = _fp8_round if precision == "fp8" else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(
            spec, rnd(a).astype(BF16), rnd(b).astype(BF16),
            preferred_element_type=F32,
        )
    return mm


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta: float):
    """Rotate-half RoPE over the last axis of ``x`` [T, ..., R] at
    positions ``pos`` [T]: dimension i turns against dimension i + R/2 by
    the angle pos * theta ** (-2i / R)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) * 2 / x.shape[-1])
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _swiglu(mm, x, gate, up, down):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, gate))
              * mm("td,df->tf", x, up), down)


def attention(x, lw: dict, d: dict, mm):
    """Expanded multi-head latent attention of ``x`` [T, D], causal."""
    T = x.shape[0]
    H, dn, dr, dv = (d["num_attention_heads"], d["qk_nope_head_dim"],
                     d["qk_rope_head_dim"], d["v_head_dim"])
    rank = d["kv_lora_rank"]
    pos = jnp.arange(T)
    c_q = _rms(mm("td,dr->tr", x, lw["q_a"]), lw["q_a_norm"], d["eps"])
    q = mm("tr,re->te", c_q, lw["q_b"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, d["rope_theta"])
    kv = mm("td,dr->tr", x, lw["kv_a"])
    c_kv = _rms(kv[:, :rank], lw["kv_a_norm"], d["eps"])
    k_rope = _rope(kv[:, rank:], pos, d["rope_theta"])  # one head for all
    kvb = mm("tr,re->te", c_kv, lw["kv_b"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions are no multiple of {qb}")

    def block(args):
        qn, qr, p = args  # [qb, H, dn], [qb, H, dr], [qb]
        s = (mm("qhe,khe->hqk", qn, k_nope) + mm("qhe,ke->hqk", qr, k_rope))
        s = s / math.sqrt(dn + dr)
        s = jnp.where(pos[None, None, :] <= p[None, :, None], s, -jnp.inf)
        return mm("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)

    split = lambda a: a.reshape(T // qb, qb, *a.shape[1:])  # noqa: E731
    o = jax.lax.map(block, (split(q_nope), split(q_rope), split(pos)))
    return mm("te,ed->td", o.reshape(T, H * dv), lw["o"])


def _scores(x, lw: dict):
    return jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(F32), lw["router"].astype(F32),
        precision=HIGHEST, preferred_element_type=F32,
    ))


def routing_margin(x, lw: dict, d: dict):
    """[T]: by how much the last chosen expert's biased score leads the
    best of those left out."""
    k = d["num_experts_per_tok"]
    top, _ = jax.lax.top_k(_scores(x, lw) + lw["router_bias"], k + 1)
    return top[:, k - 1] - top[:, k]


def route(x, lw: dict, d: dict):
    """(chosen experts [T, k], their weights [T, k]) — float32 always."""
    s = _scores(x, lw)
    _, chosen = jax.lax.top_k(s + lw["router_bias"], d["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * d["routed_scaling_factor"]


def moe(x, lw: dict, d: dict, mm):
    chosen, w = route(x, lw, d)

    def one(acc, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _swiglu(mm, x, gate, up, down), None

    E = d["n_routed_experts"]
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(E), lw["e_gate"], lw["e_up"], lw["e_down"]),
    )
    return y + _swiglu(mm, x, lw["s_gate"], lw["s_up"], lw["s_down"])


def block(x, lw: dict, d: dict, dense: bool, mm):
    """(the block's output [T, D], its routing margin [T]: infinite for a
    dense block)."""
    h = x + attention(_rms(x, lw["attn_norm"], d["eps"]), lw, d, mm)
    n = _rms(h, lw["mlp_norm"], d["eps"])
    if dense:
        y = h + _swiglu(mm, n, lw["gate"], lw["up"], lw["down"])
        return y, jnp.full(x.shape[:1], jnp.inf, F32)
    return h + moe(n, lw, d, mm), routing_margin(n, lw, d)


@functools.partial(
    jax.jit, static_argnames=("dense", "d_items", "precision")
)
def _layer(x, key, i, dense, d_items, precision):
    d = dict(d_items)
    return block(x, layer_weights(key, i, d, dense), d, dense,
                 _mm(precision))


@functools.partial(jax.jit, static_argnames=("d_items",))
def _embed(key, tokens, d_items):
    return top_weights(key, dict(d_items))["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("d_items", "precision"))
def _head(key, x, d_items, precision):
    d = dict(d_items)
    t = top_weights(key, d)
    return _mm(precision)(
        "td,dv->tv", _rms(x, t["final_norm"], d["eps"]), t["lm_head"]
    )


def last_hidden(key, tokens, d: dict, precision: str = "f32"):
    """(the last block's output [T, D], before the final norm; the least
    routing margin of each position over the expert layers [T]), one
    layer's weights drawn at a time."""
    items = tuple(sorted(d.items()))
    x = _embed(key, jnp.asarray(tokens, jnp.int32), items)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    for i in range(d["num_hidden_layers"]):
        x, m = _layer(x, key, jnp.int32(i), is_dense(i, d), items, precision)
        margin = jnp.minimum(margin, m)
    return x, margin


def logits(w: dict, tokens, d: dict, precision: str = "f32"):
    """Next-token logits [T, V], float32, of one sequence ``tokens`` [T]
    (T at most ``QUERY_BLOCK`` or a multiple of it)."""
    x, _ = last_hidden(w["key"], tokens, d, precision)
    return _head(w["key"], x, tuple(sorted(d.items())), precision)


@functools.partial(jax.jit, static_argnames=("d_items",))
def _mtp(key, h, tokens, d_items):
    d = dict(d_items)
    mm = _mm("f32")
    t, m = top_weights(key, d), mtp_weights(key, d)
    x = jnp.concatenate([
        _rms(t["embed"][tokens[1:]], m["enorm"], d["eps"]),
        _rms(h[:-1], m["hnorm"], d["eps"]),
    ], axis=-1)
    x, _ = block(mm("te,ed->td", x, m["eh_proj"]), m["block"], d, False, mm)
    return mm("td,dv->tv", _rms(x, m["final_norm"], d["eps"]), t["lm_head"])


def mtp_logits(w: dict, tokens, d: dict):
    """The multi-token-prediction module's logits [T-1, V]: row i, from
    the main model's hidden state at i and the embedding of token i+1,
    scores token i+2."""
    h, _ = last_hidden(w["key"], tokens, d)
    return _mtp(w["key"], h, jnp.asarray(tokens, jnp.int32),
                tuple(sorted(d.items())))


@jax.jit
def _readout(lg, picks):
    picked = jnp.take_along_axis(lg, picks[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1), picked


def sequence_readout(w: dict, tokens: list[int], picks: list[int], d: dict, *,
                     precision: str = "f32", pad_to: int = QUERY_BLOCK):
    """For one sequence: at every position the best next-token logit, the
    token that has it, and the logit of ``picks[position]`` (at float32,
    less the allowance of a position the stated precision leaves open: the
    module docstring). Right-padded (causal: padding changes nothing before
    it) to ``pad_to`` rounded up to whole query blocks, and where it is
    longer to a multiple of ``READOUT_PAD``, so few shapes compile; only
    three vectors and the margins leave the device."""
    n = len(tokens)
    pad_to = -(-pad_to // QUERY_BLOCK) * QUERY_BLOCK
    if n > pad_to:
        pad_to = READOUT_PAD
    T = min(d["n_positions"], -(-n // pad_to) * pad_to)
    row = np.zeros((T,), np.int32)
    row[:n] = tokens
    pk = np.zeros((T,), np.int32)
    pk[:n] = picks
    x, margin = last_hidden(w["key"], row, d, precision)
    lg = _head(w["key"], x, tuple(sorted(d.items())), precision)
    best, amax, picked = (
        np.asarray(a)[:n] for a in _readout(lg, jnp.asarray(pk))
    )
    if precision == "f32":
        # Positions the stated precision leaves open (module docstring).
        gap = best - picked
        settled = np.asarray(margin)[:n] > ROUTING_MARGIN
        picked = best - np.where(
            settled, gap, np.maximum(gap - OPEN_ALLOWANCE, 0.0)
        )
    return best, amax, picked


# ---------------------------------------------------------------------------
# what the work needs, from shapes alone (the per-layer readers' counts)
# ---------------------------------------------------------------------------


def _attn_params(d: dict) -> int:
    return sum(
        int(np.prod(s)) for n, s in _attn_shapes(d).items()
        if not n.endswith("norm")
    )


def block_matmul_params(d: dict, dense: bool, per_token: bool) -> int:
    """Matrix entries of one block: those a token multiplies
    (``per_token``: its chosen experts and the shared one) or all stored."""
    D = d["hidden_size"]
    if dense:
        return _attn_params(d) + 3 * D * d["intermediate_size"]
    E, F = d["n_routed_experts"], d["moe_intermediate_size"]
    experts = d["num_experts_per_tok"] if per_token else E
    return (_attn_params(d) + D * E
            + 3 * D * F * (experts + d["n_shared_experts"]))


def forward_flops(d: dict, n_tokens: int, ctx_sum: int,
                  n_head_rows: int) -> float:
    """Forward FLOPs of ``n_tokens`` positions through the blocks, where
    ``ctx_sum`` is the sum over those positions of the keys each attends to
    (its own included), plus the head at ``n_head_rows`` positions: every
    matrix a token multiplies (its four routed experts and the shared one),
    and the expanded attention's pairs (q.k over nope + rope, p.v over v,
    per head)."""
    L, k = d["num_hidden_layers"], d["first_k_dense_replace"]
    per_token = 2 * (k * block_matmul_params(d, True, True)
                     + (L - k) * block_matmul_params(d, False, True))
    pair = 2 * d["num_attention_heads"] * (
        d["qk_nope_head_dim"] + d["qk_rope_head_dim"] + d["v_head_dim"]
    )
    head = 2 * d["hidden_size"] * d["vocab_size"]
    return float(per_token * n_tokens + pair * L * ctx_sum
                 + head * n_head_rows)


def decode_step_bytes(d: dict, live_tokens: float, rows: int) -> float:
    """Bytes one decode step of ``rows`` lanes must read: every stored
    weight of the blocks, the final norm and the head once (bfloat16; the
    embedding's rows of this step's tokens only) and the latent of
    ``live_tokens`` cached tokens in every layer (``kv_lora_rank`` +
    ``qk_rope_head_dim`` values, whatever the pool's leaf pads them to).
    Of the work, not of the implementation."""
    L, k, D = (d["num_hidden_layers"], d["first_k_dense_replace"],
               d["hidden_size"])
    weights = (k * block_matmul_params(d, True, False)
               + (L - k) * block_matmul_params(d, False, False)
               + D * d["vocab_size"] + rows * D)
    latent = (d["kv_lora_rank"] + d["qk_rope_head_dim"]) * L * live_tokens
    return 2.0 * (weights + latent)
