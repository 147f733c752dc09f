"""Plain GPT-2: forward, loss, gradients and AdamW in straightforward
``jax.numpy`` and float32 — the reference every GPT-2 cell is held to.

It follows the published model (Radford et al. 2019; the Hugging Face
``GPT2LMHeadModel``): learned positions, pre-LN blocks, gelu_new, LN eps
1e-5, tied head, causal softmax attention scaled by 1/sqrt(head size). It
imports nothing of the program and takes nothing the program has made: its
weights come from the seed (``make_weights``), and the harness gives the
*same* weights to the program by renaming them (``program_tree``).

Departures, each on purpose:

- Biases and LN gains are drawn at random (std 0.02 around 0 and 1), where
  GPT-2 starts them at 0 and 1: a bias that is dropped or added twice would
  not show at zero.
- Weight decay goes to matrices and embeddings only (Loshchilov & Hutter;
  the GPT-2/nanoGPT recipe). The program's rule is "ndim >= 2", which also
  decays its [heads, head] shaped q/k/v biases: a departure of the program,
  1.2e-8 a step against an Adam step of 6e-6 (PERF.md, Open questions).
- ``precision`` selects the arithmetic of every matrix product: "f32"
  (float32, ``Precision.HIGHEST``: the reference), "bf16" (operands rounded
  to bfloat16, float32 accumulation) and "fp8" (operands rounded to
  float8_e4m3 forward and the incoming gradient to float8_e5m2 backward,
  each after a per-tensor power-of-two scale, float32 accumulation: the
  usual fp8 training recipe): the control of a bf16 configuration.

Memory: the blocks run under ``lax.scan`` with ``jax.checkpoint`` and the
batch in blocks of rows, so a step at the timed size fits beside nothing
else on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import flops

F32 = jnp.float32
LAYER_LEAVES = (
    "ln1_g", "ln1_b", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "o_b",
    "ln2_g", "ln2_b", "fc_w", "fc_b", "pr_w", "pr_b",
)
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b")


def dims(cfg: dict) -> dict:
    keys = ("n_layer", "n_head", "n_embd", "n_positions", "vocab_size")
    d = {k: int(cfg[k]) for k in keys}
    d["eps"] = float(cfg.get("layer_norm_epsilon", 1e-5))
    return d


def _shapes(d: dict) -> dict:
    L, D, V, P = d["n_layer"], d["n_embd"], d["vocab_size"], d["n_positions"]
    return {
        "wte": (V, D), "wpe": (P, D), "lnf_g": (D,), "lnf_b": (D,),
        "ln1_g": (L, D), "ln1_b": (L, D), "ln2_g": (L, D), "ln2_b": (L, D),
        "q_w": (L, D, D), "k_w": (L, D, D), "v_w": (L, D, D),
        "o_w": (L, D, D), "q_b": (L, D), "k_b": (L, D), "v_b": (L, D),
        "o_b": (L, D), "fc_w": (L, D, 4 * D), "fc_b": (L, 4 * D),
        "pr_w": (L, 4 * D, D), "pr_b": (L, D),
    }


def weights_from_key(key, d: dict) -> dict:
    """The weights as a pure function of a PRNG key (for use under jit).
    Per-layer leaves are stacked on a leading layer axis."""
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(d).items())):
        std = 0.01 if name == "wpe" else 0.02
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        out[name] = x + 1.0 if name.endswith("_g") else x
    return out


def batch(seed: int, index: int, batch_size: int, traffic: dict, d: dict) -> dict:
    """Batch ``index`` of a training cell's feed, a pure function of (seed,
    index): ``seq_len`` + 1 token ids a row, uniform over the vocabulary,
    every row different."""
    rng = np.random.default_rng([int(seed), int(index)])
    return {"tokens": rng.integers(
        0, d["vocab_size"], (batch_size, int(traffic["seq_len"]) + 1),
        dtype=np.int32,
    )}


def train_flops_per_sample(d: dict, traffic: dict) -> float:
    """Model FLOPs of one sample of this family (``train_mfu``)."""
    return flops.gpt2_train_flops_per_sample(d, int(traffic["seq_len"]))


def seed_key(seed: int):
    """``--seed`` is any whole number up to a little over 2**31."""
    return jax.random.PRNGKey(int(seed))


@functools.partial(jax.jit, static_argnames=("d_items",))
def _make_weights(key, d_items):
    return weights_from_key(key, dict(d_items))


def make_weights(seed: int, d: dict) -> dict:
    """All weights, float32, on the device, in one jitted call from the
    seed."""
    return _make_weights(seed_key(seed), tuple(sorted(d.items())))


def program_tree(w: dict, d: dict) -> dict:
    """The same weights under the names and shapes of the program's flax
    tree (``models/gpt2.py``): pure renaming and reshaping."""
    H, D = d["n_head"], d["n_embd"]
    hd = D // H
    blocks = {}
    for i in range(d["n_layer"]):
        proj = lambda n: {  # noqa: E731
            "kernel": w[n + "_w"][i].reshape(D, H, hd),
            "bias": w[n + "_b"][i].reshape(H, hd),
        }
        blocks[f"block_{i}"] = {
            "ln1": {"scale": w["ln1_g"][i], "bias": w["ln1_b"][i]},
            "ln2": {"scale": w["ln2_g"][i], "bias": w["ln2_b"][i]},
            "attn": {
                "query": proj("q"), "key": proj("k"), "value": proj("v"),
                "out": {
                    "kernel": w["o_w"][i].reshape(H, hd, D),
                    "bias": w["o_b"][i],
                },
            },
            "mlp": {
                "fc_in": {"kernel": w["fc_w"][i], "bias": w["fc_b"][i]},
                "fc_out": {"kernel": w["pr_w"][i], "bias": w["pr_b"][i]},
            },
        }
    return {
        "wte": {"embedding": w["wte"]},
        "wpe": {"embedding": w["wpe"]},
        "h": blocks,
        "ln_f": {"scale": w["lnf_g"], "bias": w["lnf_b"]},
    }


_PROGRAM_NAMES = {
    ("ln1", "scale"): "ln1_g", ("ln1", "bias"): "ln1_b",
    ("ln2", "scale"): "ln2_g", ("ln2", "bias"): "ln2_b",
    ("attn", "query", "kernel"): "q_w", ("attn", "query", "bias"): "q_b",
    ("attn", "key", "kernel"): "k_w", ("attn", "key", "bias"): "k_b",
    ("attn", "value", "kernel"): "v_w", ("attn", "value", "bias"): "v_b",
    ("attn", "out", "kernel"): "o_w", ("attn", "out", "bias"): "o_b",
    ("mlp", "fc_in", "kernel"): "fc_w", ("mlp", "fc_in", "bias"): "fc_b",
    ("mlp", "fc_out", "kernel"): "pr_w", ("mlp", "fc_out", "bias"): "pr_b",
}


def leaf_names_of_program_tree(tree: dict) -> dict:
    """{leaf name as ``leaf_norms`` gives it: value} for a tree shaped like
    ``program_tree``'s (values may be anything, e.g. norms)."""
    out = {
        "wte": tree["wte"]["embedding"], "wpe": tree["wpe"]["embedding"],
        "lnf_g": tree["ln_f"]["scale"], "lnf_b": tree["ln_f"]["bias"],
    }
    for bname, block in tree["h"].items():
        i = int(bname.split("_")[1])
        for path, name in _PROGRAM_NAMES.items():
            node = block
            for p in path:
                node = node[p]
            out[f"h{i}.{name}"] = node
    return out


def leaf_norms(w: dict) -> dict:
    """{leaf name: L2 norm}, one leaf per layer for the stacked ones."""
    out = {}
    for name, x in w.items():
        if name in TOP_LEAVES:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(x))))
        else:
            per = np.asarray(
                jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
            )
            for i, v in enumerate(per):
                out[f"h{i}.{name}"] = float(v)
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _fp8_round(x, dtype=jnp.float8_e4m3fn, top: float = 448.0):
    """Round to an 8-bit float after a per-tensor power-of-two scale that
    puts the largest entry under the type's largest value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / top)))
    return (x / scale).astype(dtype).astype(F32) * scale


def _fp8_einsum(spec, a, b):
    """An fp8 product as fp8 training recipes make it: operands rounded to
    e4m3 forward, the incoming gradient rounded to e5m2 backward, float32
    accumulation. 8-bit values are exact in bfloat16 and their products in
    the float32 accumulator, so the default (one bfloat16 pass) precision
    computes exactly this."""
    mul = functools.partial(jnp.einsum, spec, preferred_element_type=F32)

    @jax.custom_vjp
    def f(a, b):
        return mul(_fp8_round(a), _fp8_round(b))

    def fwd(a, b):
        qa, qb = _fp8_round(a), _fp8_round(b)
        return mul(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(mul, *res)
        return vjp(_fp8_round(g, jnp.float8_e5m2, 57344.0))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _mm(precision: str):
    """einsum at the named arithmetic (see the module docstring)."""
    if precision == "f32":
        return functools.partial(
            jnp.einsum, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=F32,
        )
    if precision == "bf16":
        def mm(spec, a, b):
            return jnp.einsum(
                spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=F32,
            )
        return mm
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(f"unknown precision {precision!r}")


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def hidden_states(w: dict, tokens, d: dict, precision: str = "f32"):
    """Final-LN hidden states [B, T, D] of ``tokens`` [B, T]."""
    mm = _mm(precision)
    H, D = d["n_head"], d["n_embd"]
    hd = D // H
    B, T = tokens.shape
    x = w["wte"][tokens] + w["wpe"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def block(x, lw):
        h = _ln(x, lw["ln1_g"], lw["ln1_b"], d["eps"])
        q = (mm("btd,de->bte", h, lw["q_w"]) + lw["q_b"]).reshape(B, T, H, hd)
        k = (mm("btd,de->bte", h, lw["k_w"]) + lw["k_b"]).reshape(B, T, H, hd)
        v = (mm("btd,de->bte", h, lw["v_w"]) + lw["v_b"]).reshape(B, T, H, hd)
        s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = mm("bhqk,bkhd->bqhd", p, v).reshape(B, T, D)
        x = x + mm("btd,de->bte", a, lw["o_w"]) + lw["o_b"]
        h = _ln(x, lw["ln2_g"], lw["ln2_b"], d["eps"])
        h = _gelu_new(mm("btd,de->bte", h, lw["fc_w"]) + lw["fc_b"])
        x = x + mm("bte,ed->btd", h, lw["pr_w"]) + lw["pr_b"]
        return x, None

    x, _ = jax.lax.scan(block, x, {k: w[k] for k in LAYER_LEAVES})
    return _ln(x, w["lnf_g"], w["lnf_b"], d["eps"])


def logits(w: dict, tokens, d: dict, precision: str = "f32"):
    """Next-token logits [B, T, V], float32."""
    h = hidden_states(w, tokens, d, precision)
    return _mm(precision)("btd,vd->btv", h, w["wte"])


def _sum_xent(w, tokens, d, precision):
    lg = logits(w, tokens[:, :-1], d, precision)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(
    jax.jit, static_argnames=("d_items", "precision"), donate_argnums=(2,)
)
def _accumulate(w, tokens, acc, d_items, precision):
    d = dict(d_items)
    loss, g = jax.value_and_grad(_sum_xent)(w, tokens, d, precision)
    return loss, jax.tree.map(jnp.add, acc, g)


def loss_and_grads(w: dict, tokens: np.ndarray, d: dict, *,
                   precision: str = "f32", rows_per_block: int = 2):
    """Mean next-token cross-entropy over ``tokens`` [B, T+1] and its
    gradient, in blocks of rows."""
    items = tuple(sorted(d.items()))
    acc = jax.tree.map(jnp.zeros_like, w)
    total = 0.0
    B = tokens.shape[0]
    for lo in range(0, B, rows_per_block):
        part, acc = _accumulate(
            w, jnp.asarray(tokens[lo:lo + rows_per_block]), acc, items,
            precision,
        )
        total += float(part)
    n = B * (tokens.shape[1] - 1)
    return total / n, jax.tree.map(lambda g: g / n, acc)


def lr_at(count: int, o: dict) -> float:
    """optax.warmup_cosine_decay_schedule(0, lr, warmup, total) at
    ``count`` (the number of updates already made)."""
    warm, total = int(o["warmup_steps"]), int(o["total_steps"])
    total = max(total, warm + 1)
    if o.get("schedule", "cosine") == "constant":
        return float(o["lr"])
    if count < warm:
        return float(o["lr"]) * count / warm
    frac = min(1.0, (count - warm) / (total - warm))
    return float(o["lr"]) * 0.5 * (1.0 + math.cos(math.pi * frac))


def clip_by_global_norm(g: dict, clip: float) -> dict:
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = clip / jnp.maximum(norm, clip)
    return jax.tree.map(lambda x: x * scale, g)


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
    donate_argnums=(1, 2, 3),
)
def _adamw(w, g, mu, nu, lr, t, *, b1, b2, eps, wd):
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1, c2 = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)

    def upd(name):
        decay = wd if name in ("wte", "wpe") or name.endswith("_w") else 0.0
        step = mu[name] * c1 / (jnp.sqrt(nu[name] * c2) + eps)
        return w[name] - lr * (step + decay * w[name])

    return {name: upd(name) for name in w}, mu, nu


def train_steps(seed: int, batches: list, d: dict, o: dict, *,
                precision: str = "f32", rows_per_block: int = 2,
                half_batch: bool = False) -> dict:
    """The first ``len(batches)`` AdamW steps from the seed's weights, over
    the batches (``{"tokens": [B, T+1]}``) that the program's feed gave.

    Returns each step's loss, the per-leaf norms of the first gradient as
    the optimizer gets it (after the global-norm clip) and of the
    parameters' change over all the steps. ``half_batch`` plants the fault
    "half of the batch left out, the mean taken over the rest".
    """
    w0 = make_weights(seed, d)
    w = w0
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses, grad_norms = [], None
    for count, b in enumerate(batches):
        tokens = np.asarray(b["tokens"])
        if half_batch:
            tokens = tokens[: max(1, tokens.shape[0] // 2)]
        loss, g = loss_and_grads(
            w, tokens, d, precision=precision, rows_per_block=rows_per_block
        )
        if o.get("grad_clip"):
            g = clip_by_global_norm(g, float(o["grad_clip"]))
        if grad_norms is None:
            grad_norms = leaf_norms(g)
        w, mu, nu = _adamw(
            w, g, mu, nu, jnp.float32(lr_at(count, o)),
            jnp.float32(count + 1), b1=float(o["b1"]), b2=float(o["b2"]),
            eps=float(o["eps"]), wd=float(o["weight_decay"]),
        )
        losses.append(loss)
    delta_norms = leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


@functools.partial(jax.jit, static_argnames=("d_items", "precision"))
def _best_and_picked(w, tokens, picks, d_items, precision):
    lg = logits(w, tokens, dict(d_items), precision)[0]
    picked = jnp.take_along_axis(lg, picks[0][:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1), picked


def sequence_readout(w: dict, tokens: list[int], picks: list[int], d: dict, *,
                     precision: str = "f32", pad_to: int = 128):
    """For one sequence: at every position the best next-token logit, the
    token that has it, and the logit of ``picks[position]``. Right-padded to
    a multiple of ``pad_to`` (causal: padding changes nothing before it), so
    few shapes compile; only three vectors leave the device."""
    n = len(tokens)
    T = min(d["n_positions"], -(-n // pad_to) * pad_to)
    row = np.zeros((1, T), np.int32)
    row[0, :n] = tokens
    pk = np.zeros((1, T), np.int32)
    pk[0, :n] = picks
    items = tuple(sorted(d.items()))
    best, amax, picked = _best_and_picked(
        w, jnp.asarray(row), jnp.asarray(pk), items, precision
    )
    return (np.asarray(best)[:n], np.asarray(amax)[:n],
            np.asarray(picked)[:n])
