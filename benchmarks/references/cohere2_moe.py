"""Plain Command A+ (``cohere2_moe``): the forward pass in straightforward
``jax.numpy`` and float32 — the reference the served cell is held to.

It follows the published configuration
(huggingface.co/CohereLabs/command-a-plus-05-2026 ``config.json``, as the
catalog row of the ``model-configs`` guide gives it). Block l, a parallel
block (``use_parallel_block``)::

    h  = LN(x)                       # mean and variance over the width,
                                     # eps layer_norm_eps, a gain, no bias
    x' = x + Attn_l(h) + FFN(h)

``Attn_l``: ``q = h Wq`` (``num_attention_heads`` heads of ``head_dim``),
``k = h Wk``, ``v = h Wv`` (``num_key_value_heads`` heads), no bias, no q/k
norm; query head i reads KV head ``i // (heads / kv_heads)``; scale
``1 / sqrt(head_dim)``; output ``concat(heads) Wo``. Where
``layer_types[l] == "sliding_attention"``: rotary positions on q and k over
the whole head (``rotary_pct`` 1, ``rope_theta``, pairs (2i, 2i + 1):
``rope_gptj``) and query t sees keys j with ``t - sliding_window < j <= t``.
Where it is ``"full_attention"``: no positional encoding at all, causal.

``FFN`` (every layer; ``first_k_dense_replace`` 0): ``s = sigmoid(h Wr)`` in
float32 over all published experts; the ``num_experts_per_tok`` largest are
chosen, ``w = s_chosen / sum(s_chosen)`` (``norm_topk_prob``); no selection
bias, no routed scale. ``E(h) = Wdown(silu(Wgate h) * Wup h)`` at width
``intermediate_size``. ``num_shared_experts`` shared experts of the same
width, ``shared_expert_combination_strategy = "average"``::

    FFN(h) = sum_k w_k E_k(h) + (1 / n_shared) sum_s S_s(h)

Top: ``logits = logit_scale * LN_f(x) E^T`` on the tied embedding.

No cache, no batching, no kernels. It imports nothing of the program: its
weights come from the seed, and the harness gives the same weights to the
program by renaming them (``program_tree``).

**The chip's share.** The configuration's ``num_experts`` counts the experts
HELD here (``published.num_experts`` is the router's width); they are
experts ``held_first_expert .. + num_experts`` of the published ones. The
router scores all published experts and a token's weights are normalised
over all it chose; what the experts held elsewhere would have added is left
out, here and in the program alike, and the partial result goes on to the
next layer. Every expert is drawn from a key of its own (its published
index), so the shares of one seed are shares of one model
(``tests/test_cohere2_moe.py`` adds four of them up to the uncut layer). A
sliced vocabulary is a smaller ``vocab_size``.

Departures and assumptions, each on purpose (the configuration file lists
them under ``assumed``):

- The checkpoint is the seed's weights **rounded to bfloat16 once**; the
  reference computes in float32 on those same values. Matrices are normal
  with std 0.02, norm gains 1 + 0.02 n.
- The shared experts' average is over the ``num_shared_experts`` of them and
  their mean is added to the routed sum unweighted (the key's name and the
  catalog's "shared experts averaged" both say so). The program holds them
  as one SwiGLU of width ``n_shared x intermediate_size`` and divides by
  ``n_shared``: the same mathematics; ``program_tree`` lays them side by
  side.
- The window's edge: a query sees ``sliding_window`` keys, its own counted.
- RoPE pairs as published, in the program too: no column is permuted.
- The vision tower is left out (the catalog's ``config`` is the language
  model's and the cell serves token ids).
- Every held expert is evaluated on every token and weighted by its routing
  weight, which is exactly 0 for a token not routed to it.
- ``precision`` selects the arithmetic of every matrix product but the
  router's, which is float32 always: "f32" (``Precision.HIGHEST``: the
  reference), "bf16" and "fp8" (operands rounded to float8_e4m3 after a
  per-tensor power-of-two scale, float32 accumulation): the control of a
  bfloat16 configuration.

Positions the stated precision leaves open: as
``references/glm4_moe_lite.py`` (its docstring; PERF.md §6, PR 26 and
PR 30). Top-k routing is a step function: where a token's last chosen expert
leads the best one left out by less than the rounding of bfloat16 moves the
scores, the program and this reference choose different experts, and the
token's logits differ by as much as the control's do. ``sequence_readout``
holds a position to the limit as it stands where its own float32 routing is
decided by more than ``ROUTING_MARGIN`` in every layer, and takes
``OPEN_ALLOWANCE`` off its gap elsewhere. Both from readings on the chip at
the cell's size (PERF.md §6, PR 30): with 128 scores and 8 chosen a margin
of 0.002 holds 319-512 of a run's 794-1,198 generated positions to the limit
(a third to a half; 0.006, the sibling file's, held 45-104). Over nine sound
runs through the harness the widest gap at a held position was 0.021, where
the fp8 control reads 0.50-0.58 and an altered token 7.1-8.6; at a margin
of 0.0015 the widest was 0.064 and at 0.001 it was 0.34, over the limit (a
flipped expert), so 0.002 is the least of the margins read that leaves room
under the limit. At the open positions the widest raw gap of ten runs was
0.34 and an altered token lies 3.9-4.8 below, so 2.0 is taken off. The margin is over all published experts: a
change between two experts held elsewhere moves the result here only through
the normalisation, and is counted as open all the same.

Memory: one layer is 1,150M parameters here, 4.6 GB in float32.
``make_weights`` returns a handle (the key), ``sequence_readout`` makes one
layer's weights from the key at a time, and attention runs in blocks of
queries, so a 13k-token sequence fits once the engine is freed.
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
QUERY_BLOCK = 128  # queries attended at once: [heads, 128, keys] scores
# "Positions the stated precision leaves open" (module docstring): the
# least routing margin at which a position is held to the limit as it
# stands, and what a served token may lie below the best by at the others.
ROUTING_MARGIN = 0.002
OPEN_ALLOWANCE = 2.0
# sequence_readout pads a long sequence to a multiple of this: at most four
# lengths (two programs each, a window and a global layer's) up to 16384.
READOUT_PAD = 4096

_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
    "num_shared_experts", "num_experts_per_tok", "vocab_size",
    "sliding_window",
)
_AS_BUILT = {
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "use_parallel_block": True, "first_k_dense_replace": 0,
    "shared_expert_combination_strategy": "average",
    "position_embedding_type": "rope_gptj", "use_qk_norm": False,
    "attention_bias": False, "tie_word_embeddings": True, "rotary_pct": 1,
    "hidden_act": "silu",
}


def dims(cfg: dict) -> dict:
    for key, built in _AS_BUILT.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"only {key}={built!r} is built, got {cfg[key]!r}")
    d = {k: int(cfg[k]) for k in _KEYS}
    # the router's width: the published count, of which num_experts are
    # held here, from held_first_expert on
    d["published_experts"] = int(
        cfg.get("published", {}).get("num_experts", d["num_experts"])
    )
    d["held_first_expert"] = int(cfg.get("held_first_expert", 0))
    d["layer_types"] = tuple(cfg["layer_types"])
    if len(d["layer_types"]) != d["num_hidden_layers"]:
        raise ValueError("layer_types names another number of layers")
    d["rope_theta"] = float(cfg["rope_theta"])
    d["eps"] = float(cfg["layer_norm_eps"])
    d["logit_scale"] = float(cfg.get("logit_scale", 1))
    d["n_positions"] = int(cfg["max_position_embeddings"])
    d["torch_dtype"] = str(cfg.get("torch_dtype", "bfloat16"))
    return d


def seed_key(seed: int):
    """``--seed`` is any whole number up to a little over 2**31."""
    return jax.random.PRNGKey(int(seed))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def layer_shapes(d: dict) -> dict:
    """{leaf: shape} of one block but for its routed experts."""
    D, F = d["hidden_size"], d["intermediate_size"]
    H, G, Dh = (d["num_attention_heads"], d["num_key_value_heads"],
                d["head_dim"])
    S = d["num_shared_experts"]
    return {
        "norm": (D,), "q": (D, H * Dh), "k": (D, G * Dh), "v": (D, G * Dh),
        "o": (H * Dh, D), "router": (D, d["published_experts"]),
        "s_gate": (S, D, F), "s_up": (S, D, F), "s_down": (S, F, D),
    }


def _draw(key, name: str, shape) -> jax.Array:
    """One leaf from its key: the checkpoint's value, float32 holding a
    bfloat16-representable number."""
    x = 0.02 * jax.random.normal(key, shape, F32)
    x = x + (1.0 if name.endswith("norm") else 0.0)
    # Not ``.astype(bfloat16).astype(float32)``: under jit the TPU's
    # compiler drops that round trip as excess precision it may keep
    # (PERF.md §6, PR 26).
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _leaves(key, shapes: dict) -> dict:
    return {
        name: _draw(jax.random.fold_in(key, i), name, shape)
        for i, (name, shape) in enumerate(sorted(shapes.items()))
    }


def expert_weights(key, i, d: dict) -> dict:
    """The held experts of block ``i``, each drawn from the key of its
    PUBLISHED index: ``e_gate``, ``e_up`` [held, D, F], ``e_down``."""
    D, F = d["hidden_size"], d["intermediate_size"]
    lkey = jax.random.fold_in(jax.random.fold_in(key, 1 + i), 1 << 16)

    def one(e):
        return _leaves(
            jax.random.fold_in(lkey, e),
            {"e_gate": (D, F), "e_up": (D, F), "e_down": (F, D)},
        )

    return jax.vmap(one)(
        d["held_first_expert"] + jnp.arange(d["num_experts"])
    )


def layer_weights(key, i, d: dict) -> dict:
    """Block ``i``'s weights from the run's key (pure: usable under jit,
    where ``i`` may be traced: one program serves every layer of a kind)."""
    out = _leaves(jax.random.fold_in(key, 1 + i), layer_shapes(d))
    out.update(expert_weights(key, i, d))
    return out


def top_weights(key, d: dict) -> dict:
    return _leaves(
        jax.random.fold_in(key, 0),
        {"embed": (d["vocab_size"], d["hidden_size"]),
         "final_norm": (d["hidden_size"],)},
    )


def weights_from_key(key, d: dict) -> dict:
    """Every weight as a pure function of a PRNG key (for use under jit,
    where each leaf is drawn, rounded and handed on without a float32 copy
    of the whole): ``top`` and ``layers`` (a list)."""
    return {
        "top": top_weights(key, d),
        "layers": [
            layer_weights(key, i, d) for i in range(d["num_hidden_layers"])
        ],
    }


def make_weights(seed: int, d: dict) -> dict:
    """A handle, not the weights: ``sequence_readout`` draws one layer at a
    time from it (float32 copies of all of them do not fit a chip)."""
    return {"key": seed_key(seed)}


# -- the program's names ----------------------------------------------------


def _block_tree(lw: dict, d: dict, dt) -> dict:
    H, G, Dh = (d["num_attention_heads"], d["num_key_value_heads"],
                d["head_dim"])
    D = d["hidden_size"]
    m = lambda x: x.astype(dt)  # noqa: E731  (matrices: the stated dtype)
    # the shared experts side by side: one SwiGLU of width n_shared x F
    wide = lambda w: jnp.moveaxis(w, 0, 1).reshape(D, -1)  # noqa: E731
    return {
        "norm": {"scale": lw["norm"]},
        "attn": {
            "q": {"kernel": m(lw["q"]).reshape(D, H, Dh)},
            "k": {"kernel": m(lw["k"]).reshape(D, G, Dh)},
            "v": {"kernel": m(lw["v"]).reshape(D, G, Dh)},
            "out": {"kernel": m(lw["o"]).reshape(H, Dh, D)},
        },
        "moe": {
            "router": m(lw["router"]),
            "experts_gate": m(lw["e_gate"]), "experts_up": m(lw["e_up"]),
            "experts_down": m(lw["e_down"]),
            "shared": {
                "gate": {"kernel": m(wide(lw["s_gate"]))},
                "up": {"kernel": m(wide(lw["s_up"]))},
                "down": {"kernel": m(lw["s_down"]).reshape(-1, D)},
            },
        },
    }


def program_tree(w: dict, d: dict) -> dict:
    """The same weights under the names, shapes and dtypes of the
    program's flax tree (``models/cohere2_moe.py``): matrices in the
    checkpoint's dtype, gains float32."""
    dt = jnp.dtype(d["torch_dtype"])
    t = w["top"]
    out = {
        "embed": {"embedding": t["embed"].astype(dt)},
        "norm": {"scale": t["final_norm"]},
    }
    for i, lw in enumerate(w["layers"]):
        out[f"block_{i}"] = _block_tree(lw, d, dt)
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


def _ln(x, g, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g


def _rope(x, pos, theta: float):
    """RoPE over the last axis of ``x`` [T, H, R] at positions ``pos`` [T]:
    dimension 2i turns against dimension 2i + 1 by the angle
    pos * theta ** (-2i / R)."""
    R = x.shape[-1]
    inv = theta ** (-np.arange(R // 2, dtype=np.float32) * 2 / R)
    ang = pos.astype(F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _swiglu(mm, x, gate, up, down):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, gate))
              * mm("td,df->tf", x, up), down)


def attention(h, lw: dict, d: dict, window: bool, mm):
    """Grouped-query attention of the normed input ``h`` [T, D]: a window
    layer (rotary positions, the band) or a global one (none, causal)."""
    T = h.shape[0]
    H, G, Dh = (d["num_attention_heads"], d["num_key_value_heads"],
                d["head_dim"])
    pos = jnp.arange(T)
    q = mm("td,de->te", h, lw["q"]).reshape(T, H, Dh)
    k = mm("td,de->te", h, lw["k"]).reshape(T, G, Dh)
    v = mm("td,de->te", h, lw["v"]).reshape(T, G, Dh)
    if window:
        q, k = (_rope(a, pos, d["rope_theta"]) for a in (q, k))
    q = q.reshape(T, G, H // G, Dh)  # query head g * rep + r reads KV head g
    qb = min(QUERY_BLOCK, T)
    if T % qb:
        raise ValueError(f"{T} positions are no multiple of {qb}")

    def block(args):
        qq, p = args  # [qb, G, rep, Dh], [qb]
        s = mm("qgrd,kgd->grqk", qq, k) / math.sqrt(Dh)
        seen = pos[None, :] <= p[:, None]
        if window:
            seen &= pos[None, :] > p[:, None] - d["sliding_window"]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return mm("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    split = lambda a: a.reshape(T // qb, qb, *a.shape[1:])  # noqa: E731
    o = jax.lax.map(block, (split(q), split(pos)))
    return mm("te,ed->td", o.reshape(T, H * Dh), lw["o"])


def _scores(h, lw: dict):
    return jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(F32), lw["router"].astype(F32),
        precision=HIGHEST, preferred_element_type=F32,
    ))


def routing_margin(h, lw: dict, d: dict):
    """[T]: by how much the last chosen expert's score leads the best of
    those left out, over all published experts."""
    k = d["num_experts_per_tok"]
    top, _ = jax.lax.top_k(_scores(h, lw), k + 1)
    return top[:, k - 1] - top[:, k]


def route(h, lw: dict, d: dict):
    """(chosen experts [T, k] by published index, their weights [T, k],
    summing to 1) — float32 always."""
    s = _scores(h, lw)
    _, chosen = jax.lax.top_k(s, d["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def routed(h, lw: dict, d: dict, mm):
    """The held experts' part of ``sum_k w_k E_k(h)``."""
    chosen, w = route(h, lw, d)

    def one(acc, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [T]
        return acc + w_e[:, None] * _swiglu(mm, h, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (d["held_first_expert"] + jnp.arange(d["num_experts"]),
         lw["e_gate"], lw["e_up"], lw["e_down"]),
    )
    return y


def shared(h, lw: dict, d: dict, mm):
    """The mean of the shared experts."""
    y = jnp.zeros_like(h)
    for s in range(d["num_shared_experts"]):
        y = y + _swiglu(mm, h, lw["s_gate"][s], lw["s_up"][s],
                        lw["s_down"][s])
    return y / d["num_shared_experts"]


def block(x, lw: dict, d: dict, window: bool, mm):
    """(the block's output [T, D], its routing margin [T])."""
    h = _ln(x, lw["norm"], d["eps"])
    y = (x + attention(h, lw, d, window, mm) + routed(h, lw, d, mm)
         + shared(h, lw, d, mm))
    return y, routing_margin(h, lw, d)


def is_window(i: int, d: dict) -> bool:
    return d["layer_types"][i] == "sliding_attention"


@functools.partial(
    jax.jit, static_argnames=("window", "d_items", "precision")
)
def _layer(x, key, i, window, d_items, precision):
    d = dict(d_items)
    return block(x, layer_weights(key, i, d), d, window, _mm(precision))


@functools.partial(jax.jit, static_argnames=("d_items",))
def _embed(key, tokens, d_items):
    return top_weights(key, dict(d_items))["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("d_items", "precision"))
def _head(key, x, d_items, precision):
    d = dict(d_items)
    t = top_weights(key, d)
    return d["logit_scale"] * _mm(precision)(
        "td,vd->tv", _ln(x, t["final_norm"], d["eps"]), t["embed"]
    )


def last_hidden(key, tokens, d: dict, precision: str = "f32"):
    """(the last block's output [T, D], before the final norm; the least
    routing margin of each position over the layers [T]), one layer's
    weights drawn at a time."""
    items = tuple(sorted(d.items()))
    x = _embed(key, jnp.asarray(tokens, jnp.int32), items)
    margin = jnp.full(x.shape[:1], jnp.inf, F32)
    for i in range(d["num_hidden_layers"]):
        x, m = _layer(x, key, jnp.int32(i), is_window(i, d), items, precision)
        margin = jnp.minimum(margin, m)
    return x, margin


def logits(w: dict, tokens, d: dict, precision: str = "f32"):
    """Next-token logits [T, V], float32, of one sequence ``tokens`` [T]
    (T at most ``QUERY_BLOCK`` or a multiple of it)."""
    x, _ = last_hidden(w["key"], tokens, d, precision)
    return _head(w["key"], x, tuple(sorted(d.items())), precision)


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
    D, Dh = d["hidden_size"], d["head_dim"]
    return 2 * D * Dh * (d["num_attention_heads"] + d["num_key_value_heads"])


def _expert_params(d: dict) -> int:
    return 3 * d["hidden_size"] * d["intermediate_size"]


def _outside_experts(d: dict) -> int:
    """Matrix entries of one block that every token multiplies: attention,
    the router, the shared experts."""
    return (_attn_params(d) + d["hidden_size"] * d["published_experts"]
            + d["num_shared_experts"] * _expert_params(d))


def _window_layers(d: dict) -> int:
    return sum(t == "sliding_attention" for t in d["layer_types"])


def forward_flops(d: dict, n_tokens: int, ctx_sum: int,
                  n_head_rows: int) -> float:
    """Forward FLOPs of ``n_tokens`` positions through the blocks, where
    ``ctx_sum`` is the sum over those positions of the keys each would
    attend to under the causal mask (its own included), plus the head at
    ``n_head_rows`` positions: every matrix a token multiplies (attention,
    the router, the shared experts, and of the routed experts ``top_k x
    held / published`` a token a layer: an EXPECTATION, what even routing
    sends to the held ones; 1 at the cell), and attention's pairs (q.k and
    p.v over ``head_dim``, per query head), which a window layer caps at the
    window: exactly for a prompt from position 0 (``ctx_sum`` the ramp
    n (n + 1) / 2), and for any other set of positions by ``n x min(mean
    context, window)``, which is at least their sum of ``min(context,
    window)``: an upper reading of the least."""
    L, W = d["num_hidden_layers"], d["sliding_window"]
    held = (d["num_experts_per_tok"] * d["num_experts"]
            / d["published_experts"])
    per_token = 2 * L * (_outside_experts(d) + held * _expert_params(d))
    if 2 * ctx_sum == n_tokens * (n_tokens + 1):  # a prompt from 0
        m = min(n_tokens, W)
        window_ctx = m * (m + 1) / 2 + (n_tokens - m) * W
    else:
        window_ctx = n_tokens * min(ctx_sum / max(1, n_tokens), W)
    n_window = _window_layers(d)
    pair = 4 * d["num_attention_heads"] * d["head_dim"]
    head = 2 * d["hidden_size"] * d["vocab_size"]
    return float(
        per_token * n_tokens
        + pair * ((L - n_window) * ctx_sum + n_window * window_ctx)
        + head * n_head_rows
    )


def decode_step_bytes(d: dict, live_tokens: float, rows: int) -> float:
    """Bytes one decode step of ``rows`` lanes must read, whatever
    implements it (bfloat16): every weight outside the routed experts once;
    of the held experts of a layer those that ``rows`` tokens touch in
    expectation, ``held x (1 - (1 - top_k / published) ** rows)`` (10.3 of
    16 at 16 rows); the head's rows of the vocabulary and the final norm
    (the embedding's rows of this step's tokens are among them); and the K
    and V of the live tokens: all ``live_tokens`` of them in a global layer,
    in a window layer no more than ``min(live_tokens, rows x window)``. The
    last is an upper reading of the least, which is the sum over lanes of
    ``min(lane, window)``: the two agree where every lane is past the window
    or none is, and at the cell's lengths (lanes of 0.5k-13k tokens, 5.3k at
    the mean, 48% of them under 4,096) the bound reads 65.5k tokens a window
    layer where the lanes' own sum is 53.3k, so the whole count is about 2%
    over (0.15 GB of 8.3 GB)."""
    L, D = d["num_hidden_layers"], d["hidden_size"]
    touched = d["num_experts"] * (
        1.0 - (1.0 - d["num_experts_per_tok"] / d["published_experts"])
        ** rows
    )
    weights = (L * (_outside_experts(d) + touched * _expert_params(d))
               + D * d["vocab_size"])
    n_window = _window_layers(d)
    kv = 2 * d["num_key_value_heads"] * d["head_dim"] * (
        (L - n_window) * live_tokens
        + n_window * min(live_tokens, rows * d["sliding_window"])
    )
    return 2.0 * (weights + kv)
