"""Trainer core — the TPU-native counterpart of the reference's "CUDA/NCCL
distributed trainer" (``BASELINE.json:5``).

Everything the reference does with explicit rank orchestration happens here
inside ONE compiled program over a mesh:

- gradient sync: the loss is a mean over the *global* (sharded) batch, so
  ``jax.grad`` + the XLA partitioner emit the all-reduce that NCCL performed
  explicitly in the reference;
- parameter broadcast at init: ``jax.jit(init, out_shardings=...)`` places
  freshly initialized params according to their NamedShardings (replicated
  axes = the broadcast);
- optimizer step: an optax update fused by XLA into the step program (the
  reference's hand-written CUDA optimizer kernel);
- ZeRO-1 / FSDP / TP: purely a change of the sharding rules applied to the
  state tree — no trainer code change (see ``parallel/``).
"""

from __future__ import annotations

import dataclasses
import time
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from jax.sharding import Mesh

from .precision import (
    Policy,
    cast_grads_to_update,
    cast_to_compute,
    check_precision_composition,
    get_policy,
)
from .sharding import (
    DEFAULT_LOGICAL_RULES,
    activation_mesh,
    batch_sharding,
    logical_to_mesh_sharding,
    validate_tree_shardings,
)
from .utils.rng import fold_in_step


@struct.dataclass
class TrainState:
    """The full training state: one sharded pytree, HBM-resident.

    ``model_state`` holds non-trained collections (e.g. BatchNorm running
    stats); empty dict for pure-functional models.

    ``grad_residual`` is the error-feedback residual of the compressed
    gradient sync (``grad_comm`` in {int8, bf16}; see ``comms_quant.py``):
    per-parameter trees with a leading per-member dimension sharded over the
    ``dp`` axis (``parallel/zero.residual_shardings``). Under the overlapped
    paths (``grad_bucket_mb``/``update_sharding`` — ``comms_overlap.py``)
    the same bytes live as a tuple of per-BUCKET flat ``[dp, padded]``
    buffers instead. ``None`` — and absent from the pytree, so fp32
    checkpoints are unchanged — when ``grad_comm`` is fp32.

    ``health`` carries the on-device health guard's anomaly counters
    (``health.HealthState``; replicated scalars). Same None-when-disabled
    contract as ``grad_residual``, so guarded and unguarded checkpoints
    differ only when the guard is actually on.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any
    rng: jax.Array
    grad_residual: Any = None
    health: Any = None


# ---------------------------------------------------------------------------
# Tasks: how a model consumes a batch. Each returns (loss, metrics, updates).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Task:
    """Adapter between a model and a batch dict."""

    input_fn: Callable[[dict], tuple]  # batch -> model.__call__ positional args
    loss_fn: Callable[[Any, dict], tuple[jax.Array, dict]]  # (output, batch)


def _xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )


def classification_task(label_smoothing: float = 0.0) -> Task:
    """``label_smoothing``: standard (1-ε) one-hot + ε/K smoothing — the
    MLPerf ResNet-50 recipe uses 0.1 (BASELINE.json:2 "top-1 parity")."""

    def loss_fn(logits, batch):
        labels = batch["label"]
        xent = _xent(logits, labels).mean()
        if label_smoothing:
            k = logits.shape[-1]
            soft = optax.smooth_labels(
                jax.nn.one_hot(labels, k), label_smoothing
            )
            loss = optax.softmax_cross_entropy(
                logits.astype(jnp.float32), soft
            ).mean()
        else:
            loss = xent
        acc = (logits.argmax(-1) == labels).mean()
        # 'loss' is the training objective (smoothing raises its floor by
        # ~eps*ln(K)); 'xent' stays the plain cross-entropy so eval_xent is
        # comparable across smoothing settings and to published baselines.
        return loss, {"loss": loss, "xent": xent, "accuracy": acc}

    return Task(input_fn=lambda b: (b["image"],), loss_fn=loss_fn)


def _per_token_xent(model_out, targets, head_chunk: int):
    """Per-token xent for either head form: full [B, L, V] logits, or a
    chunked-head dict (``chunked_head=True`` models) that never
    materializes them (ops/chunked_xent.py)."""
    from .ops.chunked_xent import chunked_xent, is_chunked_head

    if is_chunked_head(model_out):
        return chunked_xent(model_out, targets, seq_chunk=head_chunk)
    return _xent(model_out, targets)


def lm_task(head_chunk: int = 128) -> Task:
    """Causal LM: predict tokens[1:] from tokens[:-1]."""

    def input_fn(batch):
        return (batch["tokens"][:, :-1],)

    def loss_fn(out, batch):
        targets = batch["tokens"][:, 1:]
        loss = _per_token_xent(out, targets, head_chunk).mean()
        # exp(mean xent) — the LM eval metric; computed on-device, so the
        # eval loop's batch-mean of it is the standard per-batch-ppl mean.
        return loss, {"loss": loss, "perplexity": jnp.exp(loss)}

    return Task(input_fn=input_fn, loss_fn=loss_fn)


def mlm_task(head_chunk: int = 128) -> Task:
    """Masked LM: loss only on masked positions (labels == -1 is ignored).

    Padded batches: when the dataset emits an ``attention_mask`` (e.g.
    ``synthetic_mlm`` with ``pad_min_len``), it is fed to the model as the
    key-padding mask; padding positions carry label -1, so they are already
    outside the loss."""

    def input_fn(batch):
        if "attention_mask" in batch:
            return (batch["input_tokens"], batch["attention_mask"])
        return (batch["input_tokens"],)

    def loss_fn(out, batch):
        labels = batch["labels"]
        weights = (labels >= 0).astype(jnp.float32)
        per_tok = _per_token_xent(out, jnp.maximum(labels, 0), head_chunk)
        loss = (per_tok * weights).sum() / jnp.maximum(weights.sum(), 1.0)
        return loss, {"loss": loss, "masked_fraction": weights.mean()}

    return Task(input_fn=input_fn, loss_fn=loss_fn)


def get_task(name: str, **task_kwargs) -> Task:
    """``task_kwargs``: per-task knobs (lm/mlm: ``head_chunk`` — sequence
    positions per chunked-xent scan step when the model opts into
    ``chunked_head``; ignored for full-logits models). A knob another
    task declares is dropped for tasks that don't take it (callers pass
    the full knob set); a knob NO task declares is a loud TypeError, so
    a wiring typo can't silently train with defaults."""
    import inspect

    factories = {
        "classification": classification_task,
        "lm": lm_task,
        "mlm": mlm_task,
    }
    known = {
        p for f in factories.values()
        for p in inspect.signature(f).parameters
    }
    unknown = set(task_kwargs) - known
    if unknown:
        raise TypeError(f"unknown task knob(s) {sorted(unknown)}")
    factory = factories[name]
    params = inspect.signature(factory).parameters
    return factory(**{k: v for k, v in task_kwargs.items() if k in params})


# ---------------------------------------------------------------------------
# Optimizer factory
# ---------------------------------------------------------------------------


class LowPrecisionAdamWState(NamedTuple):
    """AdamW state with moments stored in a low-precision dtype
    (``precision.py`` policy ``bf16_full``). Same (count, mu, nu) layout as
    the fused kernel's state so ``parallel/zero.shard_opt_state_shardings``
    shards it identically — but a distinct type, so ``Trainer._tx_update``'s
    ``FusedAdamWState`` shard_map dispatch never fires on it."""

    count: jax.Array
    mu: Any
    nu: Any


def low_precision_adamw(
    learning_rate,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    mask=None,
    moment_dtype=jnp.bfloat16,
    stochastic_rounding: bool = True,
) -> optax.GradientTransformation:
    """AdamW whose moment trees LIVE in ``moment_dtype`` (HBM halved vs the
    fp32 trees — the ``bf16_full`` policy) while every update is COMPUTED in
    fp32: moments are cast up, advanced, applied to the fp32 master params,
    and only the store back to ``moment_dtype`` narrows — with stochastic
    rounding (``ops/fused_adamw.stochastic_round``), since round-to-nearest
    on ``mu <- b1*mu + (1-b1)*g`` would deterministically drop any ``g``
    below one bf16 ulp of ``mu`` and the moment EMA stalls exactly like
    bf16 master weights do. Matches ``optax.adamw`` update math (bias
    correction at the incremented count, decoupled weight decay on
    ``mask``-ed leaves, schedule evaluated at the pre-increment count)."""
    from .ops.fused_adamw import stochastic_round

    sched = (
        learning_rate if callable(learning_rate)
        else optax.constant_schedule(learning_rate)
    )
    moment_dtype = jnp.dtype(moment_dtype)

    def init_fn(params):
        zeros = lambda t: jax.tree.map(  # noqa: E731
            lambda p: jnp.zeros(jnp.shape(p), moment_dtype), t
        )
        return LowPrecisionAdamWState(
            count=jnp.zeros((), jnp.int32), mu=zeros(params), nu=zeros(params)
        )

    def update_fn(grads, state, params):
        if params is None:
            raise ValueError("low_precision_adamw requires params")
        count = optax.safe_int32_increment(state.count)
        lr = sched(state.count)
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        decay = (
            mask(params) if mask is not None
            else jax.tree.map(lambda _: True, params)
        )
        # One deterministic key per (step, leaf): resume from a checkpoint
        # replays the same rounding stream — no RNG threaded through state.
        key = jax.random.fold_in(jax.random.PRNGKey(0x5F3759), count)

        flat_g, treedef = jax.tree.flatten(grads)
        flat = []
        for i, (g, mu, nu, p, d) in enumerate(zip(
            flat_g,
            treedef.flatten_up_to(state.mu),
            treedef.flatten_up_to(state.nu),
            treedef.flatten_up_to(params),
            treedef.flatten_up_to(decay),
        )):
            g32 = g.astype(jnp.float32)
            mu32 = b1 * mu.astype(jnp.float32) + (1.0 - b1) * g32
            nu32 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
            upd = (mu32 / c1) / (jnp.sqrt(nu32 / c2) + eps)
            if weight_decay:
                upd = jnp.where(d, upd + weight_decay * p.astype(jnp.float32), upd)
            if stochastic_rounding:
                mu_store = stochastic_round(mu32, jax.random.fold_in(key, 2 * i))
                nu_store = stochastic_round(
                    nu32, jax.random.fold_in(key, 2 * i + 1)
                )
            else:
                mu_store = mu32.astype(moment_dtype)
                nu_store = nu32.astype(moment_dtype)
            flat.append(((-lr * upd).astype(p.dtype), mu_store, nu_store))
        unflatten = lambda xs: jax.tree.unflatten(treedef, xs)  # noqa: E731
        return unflatten([f[0] for f in flat]), LowPrecisionAdamWState(
            count=count,
            mu=unflatten([f[1] for f in flat]),
            nu=unflatten([f[2] for f in flat]),
        )

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(
    name: str = "sgd",
    lr: float = 0.1,
    *,
    momentum: float = 0.9,
    b1: float = 0.9,
    b2: float = 0.999,
    weight_decay: float = 0.0,
    warmup_steps: int = 0,
    schedule: str = "constant",
    total_steps: int = 0,
    grad_clip: float = 0.0,
    precision: str | Policy = "fp32",
) -> optax.GradientTransformation:
    if schedule == "constant":
        sched = optax.constant_schedule(lr)
    elif schedule == "cosine":
        sched = optax.warmup_cosine_decay_schedule(
            0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1)
        )
    elif schedule == "linear":
        sched = optax.join_schedules(
            [
                optax.linear_schedule(0.0, lr, max(warmup_steps, 1)),
                optax.linear_schedule(lr, 0.0, max(total_steps - warmup_steps, 1)),
            ],
            [warmup_steps],
        )
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    # THE decay rule, shared with the fused kernel so the optax and Pallas
    # optimizers cannot diverge: biases / BN / norm scales are not decayed
    # (the MLPerf ResNet recipe — a real lever for BASELINE.json:2's
    # "top-1 parity").
    from .ops.fused_adamw import decay_leaf

    decay_mask = lambda params: jax.tree.map(decay_leaf, params)  # noqa: E731

    # Policy x optimizer fence (precision.py): bf16_full's low-precision
    # moments are an adamw-only state layout — fails HERE, config-time.
    policy = check_precision_composition(precision, optim_name=name)

    if name == "sgd":
        tx = optax.sgd(sched, momentum=momentum, nesterov=False)
        if weight_decay:
            tx = optax.chain(
                optax.add_decayed_weights(weight_decay, mask=decay_mask),
                tx,
            )
    elif name == "adamw":
        if policy.moment_dtype != policy.param_dtype:
            tx = low_precision_adamw(
                sched, b1=b1, b2=b2, weight_decay=weight_decay,
                mask=decay_mask, moment_dtype=policy.moment_dtype,
                stochastic_rounding=policy.stochastic_rounding,
            )
        else:
            tx = optax.adamw(
                sched, b1=b1, b2=b2, weight_decay=weight_decay, mask=decay_mask
            )
    elif name == "adamw_fused":
        from .ops.fused_adamw import fused_adamw

        # grad_clip handled inside the transformation (NOT an outer chain):
        # a chain's tuple state would hide FusedAdamWState from the
        # Trainer's shard_map dispatch (see Trainer._tx_update).
        return fused_adamw(
            sched, b1=b1, b2=b2, weight_decay=weight_decay,
            grad_clip=grad_clip,
        )
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    return tx


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


class MeshedJit:
    """A jitted function that traces/runs under the activation-mesh context.

    Model code constrains activations via ``sharding.constrain``, which
    resolves against :func:`sharding.activation_mesh`; without an active mesh
    context every activation-level constraint in the models silently vanishes
    (parameter shardings survive because they are passed explicitly via
    in/out_shardings, but seq-parallel / Ulysses layouts live purely in
    activation constraints — the round-2 silent-no-op failure). Entering the
    context around the call makes the constraints real; ``lower`` is
    forwarded under the same context so tests can assert collectives in the
    compiled HLO.
    """

    def __init__(self, fn, mesh: Mesh):
        self._fn = fn
        self._mesh = mesh

    def __call__(self, *args, **kwargs):
        with activation_mesh(self._mesh):
            return self._fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with activation_mesh(self._mesh):
            return self._fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        # Forward everything else (e.g. _cache_size) to the jitted callable.
        return getattr(self._fn, name)


class Trainer:
    """Builds the sharded init and the compiled train/eval steps.

    All sharding decisions flow from the logical-axis annotations on the
    model's parameters through ``rules`` — the same ``Trainer`` runs DP,
    FSDP, TP, ... depending only on ``mesh`` + ``rules``.
    """

    def __init__(
        self,
        model: nn.Module,
        tx: optax.GradientTransformation,
        task: Task,
        mesh: Mesh,
        rules=DEFAULT_LOGICAL_RULES,
        grad_accum: int = 1,
        zero1: bool = False,
        donate: bool = True,
        allow_idle_axes: bool = False,
        grad_comm: str = "fp32",
        grad_comm_block: int = 256,
        grad_bucket_mb: float = 0.0,
        update_sharding: str = "replicated",
        precision: str | Policy = "fp32",
        health: Any = None,
        fault_nan_step: int | None = None,
        dcn_dp: int = 1,
        comm_hierarchy: str = "auto",
    ):
        self.model = model
        # On-device health guard (health.py): a config.HealthConfig with
        # enabled=True compiles anomaly detection + skip-update into every
        # step body; anything else leaves the step untouched.
        self.health = health if (health is not None and health.enabled) else None
        # Deterministic on-device NaN fault injection
        # (fault_injection=nan:K): poisons the gradients of the step whose
        # pre-step counter equals K — the test/chaos hook for the guard.
        self.fault_nan_step = fault_nan_step
        self.tx = tx
        self.task = task
        self.mesh = mesh
        # Compressed gradient sync (comms_quant.py) fences: the lossy modes
        # replace the partitioner-emitted all-reduce with an explicit
        # shard_map ring over 'dp', which is only correct when 'dp' is the
        # ONLY model-parallel-free sync axis in play — under fsdp/tp/pp/cp/ep
        # the partitioner's gradient collectives are interleaved with
        # parameter gathers this path does not reproduce, and under
        # grad_accum the residual would need per-microbatch threading.
        # zero1 composes: it is purely optimizer-state placement downstream
        # of the (replicated) synced grads.
        from .comms_quant import GRAD_COMM_MODES

        if grad_comm not in GRAD_COMM_MODES:
            raise ValueError(
                f"grad_comm={grad_comm!r} not in {GRAD_COMM_MODES}"
            )
        if grad_comm != "fp32":
            if hasattr(model, "num_stages"):
                raise NotImplementedError(
                    f"grad_comm={grad_comm!r} x pipelined model "
                    f"{type(model).__name__} is unsupported in v1: the "
                    "pipeline engine computes grads inside its schedule — "
                    "use grad_comm='fp32'"
                )
            busy = {
                a: mesh.shape[a]
                for a in ("fsdp", "tp", "pp", "cp", "ep")
                if mesh.shape[a] > 1
            }
            if busy:
                raise NotImplementedError(
                    f"grad_comm={grad_comm!r} is pure-DP in v1 but the mesh "
                    f"has {busy}: quantized sync composes with dp/zero1 only"
                )
            if grad_accum > 1:
                raise NotImplementedError(
                    f"grad_comm={grad_comm!r} x grad_accum={grad_accum} is "
                    "unsupported in v1: accumulate-then-compress needs the "
                    "residual threaded through the microbatch scan"
                )
        self.grad_comm = grad_comm
        self.grad_comm_block = grad_comm_block
        # Overlapped bucketed sync + cross-replica weight-update sharding
        # (comms_overlap.py). Either knob routes the step through
        # _overlapped_dp_step_fn, which owns ALL wire modes (fp32 included)
        # per bucket — so the same pure-DP fences as the quantized path
        # apply: the explicit shard_map over 'dp' does not reproduce the
        # partitioner's interleaved param-gather collectives of fsdp/tp/
        # pp/cp/ep, and grad_accum would need residuals + buckets threaded
        # through the microbatch scan. Optimizer-level fences (weight_decay/
        # grad_clip/adamw_fused x sharded) are config-time in
        # comms_overlap.check_update_sharding_config via cli.build_all.
        from .comms_overlap import UPDATE_SHARDING_MODES

        if update_sharding not in UPDATE_SHARDING_MODES:
            raise ValueError(
                f"update_sharding={update_sharding!r} not in "
                f"{UPDATE_SHARDING_MODES}"
            )
        if grad_bucket_mb < 0:
            raise ValueError(
                f"grad_bucket_mb={grad_bucket_mb} must be >= 0"
            )
        self.update_sharding = update_sharding
        self.grad_bucket_mb = float(grad_bucket_mb)
        # Hierarchical ICI+DCN gradient sync (comms_hier.py;
        # docs/MULTISLICE.md): when the dp axis spans dcn_dp slices,
        # decompose each bucket's collective into intra-slice reduce-scatter
        # -> cross-slice all-reduce of the 1/ici shard (the only DCN
        # traffic) -> intra-slice all-gather. Routed through
        # _overlapped_dp_step_fn — a hierarchy is a per-bucket collective
        # choice — so the same pure-DP fences below apply to it.
        from .comms_hier import (
            HierTopology,
            check_comm_hierarchy_config,
            resolve_hierarchy,
        )

        check_comm_hierarchy_config(
            comm_hierarchy=comm_hierarchy, dcn_dp=dcn_dp,
            dp=mesh.shape["dp"],
        )
        self.comm_hierarchy = comm_hierarchy
        self.dcn_dp = dcn_dp
        self._hier_topo = (
            HierTopology(n=mesh.shape["dp"], dcn=dcn_dp)
            if resolve_hierarchy(comm_hierarchy, dcn_dp)
            else None
        )
        self._overlap = (
            self.grad_bucket_mb > 0
            or update_sharding == "sharded"
            or self._hier_topo is not None
        )
        if self._overlap:
            knobs = (
                f"grad_bucket_mb={grad_bucket_mb}"
                if self.grad_bucket_mb > 0
                else (
                    f"update_sharding={update_sharding!r}"
                    if update_sharding == "sharded"
                    else f"comm_hierarchy={comm_hierarchy!r} "
                    f"(dcn_dp={dcn_dp})"
                )
            )
            if hasattr(model, "num_stages"):
                raise NotImplementedError(
                    f"{knobs} x pipelined model {type(model).__name__} is "
                    "unsupported in v1: the pipeline engine computes grads "
                    "inside its schedule — use grad_bucket_mb=0 and "
                    "update_sharding='replicated'"
                )
            busy = {
                a: mesh.shape[a]
                for a in ("fsdp", "tp", "pp", "cp", "ep")
                if mesh.shape[a] > 1
            }
            if busy:
                raise NotImplementedError(
                    f"{knobs} is pure-DP in v1 but the mesh has {busy}: "
                    "bucketed/sharded sync composes with dp/zero1 only"
                )
            if grad_accum > 1:
                raise NotImplementedError(
                    f"{knobs} x grad_accum={grad_accum} is unsupported in "
                    "v1: per-bucket collectives (and EF residuals) would "
                    "need threading through the microbatch scan"
                )
        self._layout = None
        # Mixed-precision policy (precision.py): fp32 masters in TrainState,
        # a compute copy cast per step. Model-facing fences live here (the
        # config-time optimizer fence is check_precision_composition).
        self.precision = get_policy(precision)
        if self.precision.mixed:
            if hasattr(model, "num_stages"):
                raise NotImplementedError(
                    f"precision={self.precision.name!r} x pipelined model "
                    f"{type(model).__name__} is unsupported in v1: the 1f1b "
                    "engine differentiates inside its schedule on the "
                    "model's own dtype, so there is no seam for the "
                    "master->compute cast — use precision='fp32'"
                )
            model_dtype = jnp.dtype(getattr(model, "dtype", jnp.float32))
            if model_dtype != self.precision.compute_dtype:
                raise ValueError(
                    f"precision={self.precision.name!r} requires model.dtype"
                    f"={self.precision.compute_dtype.name!r} (got "
                    f"{model_dtype.name!r}): the step casts a "
                    f"{self.precision.compute_dtype.name} compute copy of "
                    "the fp32 masters, and a model at another dtype would "
                    "cast it straight back at every use — all cost, no win. "
                    "cli.build_all derives the model dtype from "
                    "train.precision; direct Trainer users pass "
                    "model.clone(dtype=...)"
                )
        # Composition fences (VERDICT r4 Missing #4): every {dp,fsdp,tp,pp,
        # cp,ep} pair either composes (tested) or fails HERE by name. The
        # unsupported-composition fence (pipeline x ep/cp) is unconditional;
        # the idle-axis fences (an axis no model component consumes would
        # silently replicate) honor ``allow_idle_axes`` because the HLO
        # control compiles in tests deliberately idle an axis to isolate a
        # strategy's collectives on an otherwise-identical mesh.
        if hasattr(model, "num_stages"):
            dead = {
                a: mesh.shape[a] for a in ("ep", "cp") if mesh.shape[a] > 1
            }
            if dead:
                raise NotImplementedError(
                    f"pipeline x {'/'.join(dead)} is unsupported in v1 "
                    f"(mesh has {dead}): pipelined stacks compose with "
                    "dp/fsdp/tp/zero1 only"
                )
        elif mesh.shape["pp"] > 1 and not allow_idle_axes:
            raise ValueError(
                f"mesh pp={mesh.shape['pp']} but model "
                f"{type(model).__name__} is not pipelined: the pp axis "
                "would silently replicate — use gpt2_pp/llama_pp or drop "
                "the axis"
            )
        if hasattr(model, "num_experts"):
            from .parallel.ep import check_moe_shapes

            check_moe_shapes(model.num_experts, mesh.shape["ep"])
        elif mesh.shape["ep"] > 1 and not allow_idle_axes:
            raise ValueError(
                f"mesh ep={mesh.shape['ep']} but model "
                f"{type(model).__name__} has no experts: the ep axis would "
                "silently replicate — use an MoE model (gpt2_moe/llama_moe) "
                "or drop the axis"
            )
        cp_attn = ("ring", "ring_pallas", "ulysses", "ulysses_flash")
        if (
            mesh.shape["cp"] > 1
            and not allow_idle_axes
            and not hasattr(model, "num_stages")  # fenced above
            and getattr(model, "attn_impl", None) not in cp_attn
        ):
            raise ValueError(
                f"mesh cp={mesh.shape['cp']} but model "
                f"{type(model).__name__} attention "
                f"(attn_impl={getattr(model, 'attn_impl', None)!r}) is not "
                "context-parallel: the cp axis would silently replicate — "
                f"use attn_impl in {cp_attn} or drop the axis"
            )
        self.rules = rules
        self.grad_accum = grad_accum
        self.zero1 = zero1
        self._donate = donate
        self._train_step = None
        self._fused_step = None
        self._eval_step = None
        self.state_shardings = None
        self.abstract_state = None

    # -- init ---------------------------------------------------------------

    def _bucket_layout_for(self, params):
        """The (cached) static bucket partition of the param pytree for the
        overlapped paths — pure shape math, safe to call on tracers or
        abstract params (``build_bucket_layout`` reads only shapes/dtypes,
        which are identical everywhere the Trainer sees this tree)."""
        if self._layout is None:
            from . import comms_overlap

            self._layout = comms_overlap.build_bucket_layout(
                nn.meta.unbox(params),
                self.grad_bucket_mb,
                n_members=self.mesh.shape["dp"],
                block_size=self.grad_comm_block,
            )
        return self._layout

    def _init_variables(self, rng, example_inputs):
        """(the model's freshly initialized variables, the state's rng):
        the one place ``rng`` is split, shared by the whole-state init and
        the parameters-only one."""
        p_rng, d_rng, s_rng = jax.random.split(rng, 3)
        with nn.logical_axis_rules(self.rules):
            variables = self.model.init(
                {"params": p_rng, "dropout": d_rng}, *example_inputs, train=False
            )
        return variables, s_rng

    def _init_fn(self, rng, example_inputs):
        variables, s_rng = self._init_variables(rng, example_inputs)
        params = variables.pop("params")
        # sow()-collections are per-step outputs, not persistent state.
        variables.pop("losses", None)
        variables.pop("metrics", None)
        if self.update_sharding == "sharded":
            # Flat-shard optimizer state (comms_overlap.py): tx.init runs
            # on the [dp, shard] stacked flat view of the params, so the
            # moments are BORN in the per-member layout the reduce-scatter
            # feeds — they never exist unsharded (arXiv 2004.13336).
            layout = self._bucket_layout_for(params)
            opt_state = self.tx.init(
                layout.stacked_shards(nn.meta.unbox(params))
            )
        else:
            opt_state = self.tx.init(params)
        grad_residual = None
        if self.grad_comm != "fp32":
            # EF residual: one f32 copy of the params PER dp member (leading
            # device dim, sharded over 'dp' — see setup()). Unboxed so the
            # logical-rules pass leaves it alone. The overlapped path keeps
            # its residuals per BUCKET (flat [dp, padded] buffers — the
            # granularity its codec compresses at) instead of per parameter.
            dp = self.mesh.shape["dp"]
            if self._overlap:
                from . import comms_overlap

                grad_residual = comms_overlap.zeros_bucket_residuals(
                    self._bucket_layout_for(params), dp
                )
            else:
                grad_residual = jax.tree.map(
                    lambda p: jnp.zeros((dp, *jnp.shape(p)), jnp.float32),
                    nn.meta.unbox(params),
                )
        health_state = None
        if self.health is not None:
            from .health import init_health_state

            health_state = init_health_state()
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            model_state=dict(variables),
            rng=s_rng,
            grad_residual=grad_residual,
            health=health_state,
        )

    def setup(self, example_batch) -> None:
        """Infer the state tree and its shardings (abstractly — nothing is
        materialized). Needed before ``init`` / ``train_step`` / restore."""
        if self.state_shardings is not None:
            return
        self._example_inputs = jax.tree.map(
            lambda x: jnp.asarray(x), self.task.input_fn(example_batch)
        )
        # Raw uint32 keys (not typed PRNG keys): they checkpoint as plain
        # arrays through orbax.
        abs_state = jax.eval_shape(
            lambda r: self._init_fn(r, self._example_inputs),
            jax.random.PRNGKey(0),
        )
        # Refuse silently-weaker sharding up front: a rules/mesh combination
        # that double-assigns a mesh axis on one array (flax would drop the
        # rule) or shards an indivisible dim (XLA would pad) fails HERE with
        # a named leaf, not as a quietly-replicated training run.
        validate_tree_shardings(abs_state, self.mesh, self.rules)
        specs = nn.get_partition_spec(abs_state)
        self.abstract_state = nn.meta.unbox(abs_state)
        self.state_shardings = logical_to_mesh_sharding(specs, self.mesh, self.rules)
        if self.update_sharding == "sharded":
            from .ops.fused_adamw import FusedAdamWState
            from .parallel.zero import flat_opt_state_shardings

            if isinstance(self.abstract_state.opt_state, FusedAdamWState):
                # Direct-Trainer users bypass cli.build_all's config fence;
                # the state TYPE is the first point the Trainer can see the
                # fused kernel. Same failure, still before any compile.
                raise NotImplementedError(
                    "update_sharding='sharded' x adamw_fused is unsupported "
                    "in v1: the fused kernel has its own per-leaf shard_map "
                    "dispatch (_tx_update) — use optimizer 'adamw' or "
                    "update_sharding='replicated'"
                )
            # Flat [dp, shard] moments: leading dim IS the membership.
            # zero1=True is subsumed (the state never exists unsharded),
            # so the flag composes as a no-op rather than a conflict.
            self.state_shardings = self.state_shardings.replace(
                opt_state=flat_opt_state_shardings(
                    self.abstract_state.opt_state, self.mesh
                )
            )
        elif self.zero1:
            from .parallel.zero import shard_opt_state_shardings

            self.state_shardings = self.state_shardings.replace(
                opt_state=shard_opt_state_shardings(
                    self.state_shardings.opt_state,
                    self.abstract_state.opt_state,
                    self.mesh,
                )
            )
            if self.precision.mixed and self.grad_comm == "fp32" and (
                not self._overlap
            ):
                # ZeRO-1 x mixed precision = weight-update sharding done
                # right (cf. "Automatic Cross-Replica Sharding of Weight
                # Update in Data-Parallel Training"): shard the fp32
                # MASTERS over dp like the moments — the update is
                # shard-local, and the only per-step param traffic is the
                # all-gather of the *compute-dtype copy* (the elementwise
                # cast preserves the sharded layout, so the partitioner
                # gathers bf16 — half the bytes of gathering fp32 masters).
                # Skipped under lossy grad_comm AND the overlapped paths:
                # those shard_map bodies take params with their
                # rules-derived (replicated-over-dp) in_specs, and
                # dp-sharded masters would be resharded back every step
                # for no win.
                self.state_shardings = self.state_shardings.replace(
                    params=shard_opt_state_shardings(
                        self.state_shardings.params,
                        self.abstract_state.params,
                        self.mesh,
                    )
                )
        if self.grad_comm != "fp32":
            from .parallel.zero import residual_shardings

            self.state_shardings = self.state_shardings.replace(
                grad_residual=residual_shardings(
                    self.abstract_state.grad_residual, self.mesh
                )
            )

    def init(self, seed: int, example_batch) -> TrainState:
        """Initialize and materialize the sharded TrainState.

        The placement implied by ``out_shardings`` is the TPU version of the
        reference's init-time NCCL parameter broadcast. Resume flows call
        ``setup()`` + ``CheckpointManager.restore`` instead, skipping the
        materialization entirely.
        """
        self.setup(example_batch)
        # NOT MeshedJit: placement comes from out_shardings, and flax's
        # DenseGeneral initializes kernels flat-rank-2 before reshaping — an
        # active mesh would apply the rank-3 logical constraint to the flat
        # value and fail. Activation constraints only matter in the steps.
        init = jax.jit(
            lambda r: nn.meta.unbox(self._init_fn(r, self._example_inputs)),
            out_shardings=self.state_shardings,
        )
        return init(jax.random.PRNGKey(seed))

    def init_params(self, seed: int, example_batch):
        """The parameters ``init`` would give, alone and in its placement:
        no optimizer state is built (a server never reads one, and for a
        model that fills the chip there is no room for one)."""
        self.setup(example_batch)

        def params_only(rng):
            variables, _ = self._init_variables(rng, self._example_inputs)
            return nn.meta.unbox(variables["params"])

        return jax.jit(
            params_only, out_shardings=self.state_shardings.params
        )(jax.random.PRNGKey(seed))

    def abstract_state_with_shardings(self):
        """ShapeDtypeStructs carrying shardings — what orbax needs to restore
        a checkpoint directly into the live mesh layout."""
        if self.abstract_state is None:
            raise RuntimeError("call Trainer.init() before restore")
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            self.abstract_state,
            self.state_shardings,
        )

    def lower_train_step(self, example_batch):
        """``train_step`` lowered on abstract operands shaped and sharded
        like ``example_batch`` — nothing is materialized (``setup`` is
        eval_shape-only), so it also lowers for described, unattached
        devices. ``.compile()`` it for the HLO text or the memory analysis
        of the program a run of this trainer executes."""
        self.setup(example_batch)
        bsh = batch_sharding(self.mesh)
        abs_batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x), sharding=bsh
            ),
            dict(example_batch),
        )
        return self.train_step.lower(
            self.abstract_state_with_shardings(), abs_batch
        )

    # -- steps --------------------------------------------------------------

    def _loss_and_updates(self, params, model_state, batch, rng, train: bool):
        variables = {"params": params, **model_state}
        # "losses" collects model-internal objective terms sown during the
        # forward pass (e.g. the MoE router's load-balancing loss); it is
        # folded into the objective here and never persisted into the state.
        # "metrics" collects model-internal observability scalars (e.g. the
        # router's dropped-token fraction); surfaced as train metrics.
        mutable = (
            list(model_state.keys()) + ["losses", "metrics"] if train else []
        )
        inputs = self.task.input_fn(batch)
        # Inside a shard_map body (the manual data-parallel steps) every mesh
        # axis is Manual and the rules have nothing to map onto. flax takes
        # jax's Manual ambient mesh for a global mesh and, with rules set,
        # constrains each boxed init it shape-checks — which raises. No rules
        # make that a no-op (as models/pipeline.py does for its stages);
        # ``sharding.constrain`` checks for Manual axes itself.
        manual = bool(jax.sharding.get_abstract_mesh().manual_axes)
        with nn.logical_axis_rules(() if manual else self.rules):
            if mutable:
                out, updates = self.model.apply(
                    variables, *inputs, train=train, mutable=mutable,
                    rngs={"dropout": rng},
                )
                updates = dict(updates)
            else:
                out = self.model.apply(
                    variables, *inputs, train=train, rngs={"dropout": rng}
                )
                updates = dict(model_state)
        aux = updates.pop("losses", None)
        sown_metrics = updates.pop("metrics", None)
        loss, metrics = self.task.loss_fn(out, batch)
        if aux:
            aux_total = sum(jnp.sum(v) for v in jax.tree.leaves(aux))
            loss = loss + aux_total
            metrics = {**metrics, "aux_loss": aux_total}
        if sown_metrics:
            # Aggregate by sown name across module instances (each MoE layer
            # sows its own value): the logged metric is their mean.
            groups: dict[str, list] = {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                sown_metrics
            )[0]:
                name = [p.key for p in path if hasattr(p, "key")][-1]
                groups.setdefault(name, []).append(
                    jnp.asarray(leaf, jnp.float32)
                )
            metrics = {
                **metrics,
                **{k: jnp.mean(jnp.stack(v)) for k, v in groups.items()},
            }
        return loss, (metrics, updates)

    @jax.named_scope("optimizer")  # every step variant updates through here
    def _tx_update(self, grads, opt_state, params):
        """Optimizer update; the fused Pallas AdamW runs under ``shard_map``.

        A ``pallas_call`` is an opaque custom call, so in the auto-sharded
        step the partitioner would all-gather every FSDP/ZeRO-sharded leaf
        around it (ADVICE r1 #1/#2). The update is purely elementwise, so it
        is instead run shard-local with specs taken from the *optimizer
        state's* shardings: grads and params are resharded into the moment
        layout (under ZeRO-1 that reshard IS the reduce-scatter), the kernel
        updates local shards, and the delta leaves in the moment layout (the
        step's params out_sharding turns that into the ZeRO-1 all-gather).
        Chained transforms (e.g. global-norm clipping, whose state is not a
        ``FusedAdamWState``) take the plain XLA path.
        """
        from .ops.fused_adamw import FusedAdamWState, _clip_by_global_norm

        if not isinstance(opt_state, FusedAdamWState):
            return self.tx.update(grads, opt_state, params)
        clip = getattr(self.tx, "grad_clip", 0.0)
        if clip:
            # Clip here, in the auto-sharded region, where the global norm is
            # computed over the true global grads; the (idempotent) clip
            # inside update_fn then no-ops on the per-shard views.
            grads = _clip_by_global_norm(grads, clip)
        mu_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings.opt_state.mu
        )
        state_specs = FusedAdamWState(
            count=jax.sharding.PartitionSpec(),
            mu=mu_specs,
            nu=jax.tree.map(
                lambda s: s.spec, self.state_shardings.opt_state.nu
            ),
        )
        # check_vma=False: pallas_call inside shard_map (jax 0.9.0 vma-typing
        # limitation, same as the ring/flash kernels); the body has no
        # collectives — every shard's update is independent.
        return jax.shard_map(
            self.tx.update,
            mesh=self.mesh,
            in_specs=(mu_specs, state_specs, mu_specs),
            out_specs=(mu_specs, state_specs),
            check_vma=False,
        )(grads, opt_state, params)

    def _instrument_grads(self, grads, step, metrics):
        """Shared post-gradient hook for every step body (plain / quantized
        / pipeline): deterministic NaN fault injection, then the health
        guard's grad-norm observable. Injection precedes the norm so the
        guard detects exactly what the optimizer would have consumed."""
        if self.fault_nan_step is not None:
            bad = step == self.fault_nan_step
            grads = jax.tree.map(
                lambda g: jnp.where(bad, jnp.full(g.shape, jnp.nan, g.dtype), g),
                grads,
            )
        if self.health is not None:
            metrics = {**metrics, "grad_norm": optax.global_norm(grads)}
        return grads, metrics

    def _check_accum_divides(self, batch) -> None:
        """Equal-sized microbatch groups are what makes mean-of-group-means
        equal the whole-batch mean — an uneven split would silently bias the
        loss/grads, so refuse it loudly (not as a reshape trace error)."""
        n = jax.tree.leaves(batch)[0].shape[0]
        if n % self.grad_accum:
            raise ValueError(
                f"grad_accum={self.grad_accum} must divide the global "
                f"batch size {n}"
            )

    def _pipeline_step_fn(self):
        """schedule='1f1b_interleaved': the pipeline engine computes loss AND
        grads inside one schedule (parallel/pp.interleaved_1f1b), so the step
        skips ``jax.value_and_grad`` entirely; the optimizer update is
        unchanged (incl. the fused/ZeRO shard_map dispatch).

        ``grad_accum > 1`` composes as an outer on-device scan over microbatch
        GROUPS: the batch splits into ``grad_accum`` groups, each group runs
        one full interleaved schedule (its own ``num_microbatches`` pipeline
        microbatches), and fp32 grads accumulate across groups — exactly the
        grad-accum semantics of the non-pipelined path (each group's
        loss/grads are means over its examples; the group-mean equals the
        whole-batch mean since groups are equal-sized). This keeps the
        reference's DP+accumulation workload (BASELINE.json:9) runnable under
        the framework's best pipeline schedule."""

        def one_group(params, group_batch):
            return self.model.pipeline_value_and_grad(
                params, group_batch, self.mesh
            )

        def step_fn(state: TrainState, batch):
            if self.grad_accum > 1:
                self._check_accum_divides(batch)
                groups = jax.tree.map(
                    lambda x: x.reshape(
                        (self.grad_accum, x.shape[0] // self.grad_accum)
                        + x.shape[1:]
                    ),
                    batch,
                )

                def micro(carry, group_batch):
                    loss_acc, grads_acc = carry
                    loss, grads = one_group(state.params, group_batch)
                    grads_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                    )
                    return (loss_acc + loss, grads_acc), None

                zeros = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), state.params
                )
                (loss, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zeros), groups
                )
                loss = loss / self.grad_accum
                grads = jax.tree.map(lambda g: g / self.grad_accum, grads)
            else:
                loss, grads = one_group(state.params, batch)
            grads, metrics = self._instrument_grads(
                grads, state.step, {"loss": loss}
            )
            updates_tx, new_opt_state = self._tx_update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates_tx)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
            )
            return new_state, metrics

        return step_fn

    def _quantized_dp_step_fn(self):
        """grad_comm in {int8, bf16}: explicit compressed gradient sync.

        The auto-sharded path never materializes the gradient all-reduce as
        code (the partitioner emits it from the global-batch-mean loss), so
        there is nothing to intercept — instead the WHOLE loss-and-grad
        computation runs under ``shard_map`` over the mesh: each member
        differentiates the loss of its LOCAL batch shard (a mean over
        ``B/n`` examples), then the compressed ring
        (``comms_quant.quantized_tree_all_reduce``) sums the local grads and
        ``/n`` recovers exactly the global-batch-mean gradient the fp32 path
        computes. The optimizer update stays OUTSIDE the shard_map, on the
        replicated synced grads, so the fused-AdamW / ZeRO-1 dispatch in
        :meth:`_tx_update` is unchanged.

        Plain ``jax.jit`` (not MeshedJit): the body is manual-mode, where
        ``sharding.constrain`` must stay a no-op — pure DP (fenced in
        ``__init__``) has no activation constraints to lose.
        """
        from . import comms_quant
        from jax.sharding import PartitionSpec as P

        mode = self.grad_comm
        block = self.grad_comm_block
        n = self.mesh.shape["dp"]
        param_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings.params
        )
        mstate_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings.model_state
        )
        from .mesh import BATCH_AXES

        def sync_body(params, model_state, batch, rng, residual):
            # Decorrelate per-member dropout; identical keys would tie the
            # masks across batch shards (the auto path draws one global
            # mask).
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            cparams = cast_to_compute(self.precision, params)
            (_, (metrics, updates)), grads = jax.value_and_grad(
                self._loss_and_updates, has_aux=True
            )(cparams, model_state, batch, rng, True)
            # Up-cast BEFORE the ring: ``quantized_tree_all_reduce`` owns
            # the wire compression (bf16/int8 payloads either way), and its
            # ravel_pytree unravel restores the INPUT leaf dtypes — bf16
            # grads here would silently demote both the summed grads and
            # the fp32 error-feedback residual schema.
            grads = cast_grads_to_update(self.precision, grads)
            residual = jax.tree.map(lambda r: r[0], residual)
            summed, new_residual = comms_quant.quantized_tree_all_reduce(
                grads, "dp", mode=mode, block_size=block, residual=residual
            )
            grads = jax.tree.map(lambda g: g / n, summed)
            # Local-batch means -> global-batch means (shards are equal
            # sized). Non-float model_state (e.g. counters) advances
            # identically on every member and needs no sync.
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "dp"), metrics)
            updates = jax.tree.map(
                lambda u: (
                    jax.lax.pmean(u, "dp")
                    if jnp.issubdtype(u.dtype, jnp.inexact) else u
                ),
                updates,
            )
            new_residual = jax.tree.map(lambda r: r[None], new_residual)
            return grads, metrics, updates, new_residual

        sync = jax.shard_map(
            sync_body,
            mesh=self.mesh,
            in_specs=(param_specs, mstate_specs, P(BATCH_AXES), P(), P("dp")),
            out_specs=(param_specs, P(), mstate_specs, P("dp")),
            check_vma=False,
        )

        def step_fn(state: TrainState, batch):
            rng = fold_in_step(state.rng, state.step)
            grads, metrics, updates, new_residual = sync(
                state.params, state.model_state, batch, rng,
                state.grad_residual,
            )
            # Post-sync: the norm/poison see the replicated global-mean
            # grads, the same view the optimizer consumes.
            grads, metrics = self._instrument_grads(grads, state.step, metrics)
            updates_tx, new_opt_state = self._tx_update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates_tx)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                model_state=updates,
                grad_residual=new_residual,
            )
            return new_state, metrics

        return step_fn

    def _overlapped_dp_step_fn(self):
        """Bucketed/overlapped gradient sync, optionally with cross-replica
        weight-update sharding (comms_overlap.py; docs/OVERLAP.md).

        Like :meth:`_quantized_dp_step_fn`, the whole loss-and-grad runs
        under ``shard_map`` over the mesh — but the sync is one INDEPENDENT
        collective per reverse-layer-order bucket, so XLA's scheduler can
        issue bucket k's collective while the backward dots feeding buckets
        k+1.. are still running (tests/test_overlap.py pins this in the
        scheduled HLO).

        ``update_sharding='replicated'``: per-bucket all-reduce; the
        optimizer update stays OUTSIDE the shard_map on the replicated
        synced grads — ``_instrument_grads``/``_tx_update``/ZeRO-1 dispatch
        unchanged.

        ``update_sharding='sharded'`` (arXiv 2004.13336): per-bucket
        reduce-scatter; INSIDE the body each member slices its 1/dp flat
        param shard, advances its flat-shard optimizer state (born in that
        layout — ``_init_fn``), and a per-bucket all-gather rebuilds the
        replicated params. Gradient instrumentation (NaN fault injection,
        the guard's grad-norm) moves inside too, on the shard view — the
        psum of per-shard square sums reproduces exactly the global norm
        the replicated path computes. The compiled step contains
        reduce-scatter + all-gather over 'dp' and NO full-gradient
        all-reduce.

        ``comm_hierarchy`` (comms_hier.py; docs/MULTISLICE.md): when a
        hierarchy topology is active, every per-bucket collective above is
        swapped for its two-level ICI+DCN decomposition — intra-slice
        reduce-scatter, cross-slice all-reduce of the 1/ici shard (the only
        DCN traffic), intra-slice all-gather — and under 'sharded' the
        shard member i owns becomes GLOBAL chunk ``topo.chunk_index(i)``
        for the life of the run.

        Returns the same ``(state, batch) -> (state, metrics)`` body as
        every other step fn, so the health-guard wrap and the fused K-step
        scan compose unchanged.
        """
        from jax.sharding import PartitionSpec as P

        from . import comms_overlap
        from .mesh import BATCH_AXES

        mode = self.grad_comm
        block = self.grad_comm_block
        n = self.mesh.shape["dp"]
        lossy = mode != "fp32"
        layout = self._bucket_layout_for(self.abstract_state.params)
        # Collective routing: flat (comms_overlap) vs hierarchical
        # (comms_hier) — same per-bucket call shape, so both update
        # variants below are hierarchy-agnostic. Under the hierarchy,
        # member i's reduce-scatter output is GLOBAL chunk
        # topo.chunk_index(i), so the shard index fed to
        # layout.local_shards must follow (docs/MULTISLICE.md).
        topo = self._hier_topo
        if topo is not None:
            from . import comms_hier

            def _all_reduce_buckets(grads, res):
                return comms_hier.bucketed_hier_all_reduce(
                    grads, layout, "dp", topo,
                    mode=mode, block_size=block, residuals=res,
                )

            def _reduce_scatter_buckets(grads, res):
                return comms_hier.bucketed_hier_reduce_scatter(
                    grads, layout, "dp", topo,
                    mode=mode, block_size=block, residuals=res,
                )

            def _gather_param_buckets(shards):
                return comms_hier.hier_all_gather_buckets(
                    shards, layout, "dp", topo
                )

            def _shard_index(i):
                return topo.chunk_index(i)
        else:

            def _all_reduce_buckets(grads, res):
                return comms_overlap.bucketed_all_reduce(
                    grads, layout, "dp",
                    mode=mode, block_size=block, residuals=res,
                )

            def _reduce_scatter_buckets(grads, res):
                return comms_overlap.bucketed_reduce_scatter(
                    grads, layout, "dp",
                    mode=mode, block_size=block, residuals=res,
                )

            def _gather_param_buckets(shards):
                return comms_overlap.all_gather_buckets(
                    shards, layout, "dp"
                )

            def _shard_index(i):
                return i

        param_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings.params
        )
        mstate_specs = jax.tree.map(
            lambda s: s.spec, self.state_shardings.model_state
        )

        def loss_and_local_grads(params, model_state, batch, rng):
            # Shared front half of both variants: per-member rng, compute
            # cast, local-batch loss + grads, fp32 grads for the wire.
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            cparams = cast_to_compute(self.precision, params)
            (_, (metrics, updates)), grads = jax.value_and_grad(
                self._loss_and_updates, has_aux=True
            )(cparams, model_state, batch, rng, True)
            grads = cast_grads_to_update(self.precision, grads)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "dp"), metrics)
            updates = jax.tree.map(
                lambda u: (
                    jax.lax.pmean(u, "dp")
                    if jnp.issubdtype(u.dtype, jnp.inexact) else u
                ),
                updates,
            )
            return grads, metrics, updates

        if self.update_sharding == "replicated":

            def sync_body(params, model_state, batch, rng, residual):
                grads, metrics, updates = loss_and_local_grads(
                    params, model_state, batch, rng
                )
                res = [r[0] for r in residual] if lossy else None
                summed, new_res = _all_reduce_buckets(grads, res)
                grads = jax.tree.map(lambda g: g / n, summed)
                new_res = tuple(r[None] for r in new_res) if lossy else ()
                return grads, metrics, updates, new_res

            sync = jax.shard_map(
                sync_body,
                mesh=self.mesh,
                in_specs=(
                    param_specs, mstate_specs, P(BATCH_AXES), P(), P("dp"),
                ),
                out_specs=(param_specs, P(), mstate_specs, P("dp")),
                check_vma=False,
            )

            def step_fn(state: TrainState, batch):
                rng = fold_in_step(state.rng, state.step)
                residual = state.grad_residual if lossy else ()
                grads, metrics, updates, new_res = sync(
                    state.params, state.model_state, batch, rng, residual
                )
                grads, metrics = self._instrument_grads(
                    grads, state.step, metrics
                )
                updates_tx, new_opt_state = self._tx_update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates_tx)
                new_state = state.replace(
                    step=state.step + 1,
                    params=new_params,
                    opt_state=new_opt_state,
                    model_state=updates,
                    grad_residual=new_res if lossy else None,
                )
                return new_state, metrics

            return step_fn

        # update_sharding == "sharded"
        opt_specs = jax.tree.map(
            # Flat stacks [dp, shard] carry the membership on dim 0; the
            # only other leaves an elementwise optax state can hold are
            # scalars (counts), replicated.
            lambda a: P("dp") if getattr(a, "ndim", 0) == 2 else P(),
            self.abstract_state.opt_state,
        )

        def sync_body(params, model_state, batch, rng, residual, opt_state,
                      step):
            grads, metrics, updates = loss_and_local_grads(
                params, model_state, batch, rng
            )
            res = [r[0] for r in residual] if lossy else None
            shard_grads, new_res = _reduce_scatter_buckets(grads, res)
            shard_grads = tuple(g / n for g in shard_grads)
            # _instrument_grads, shard-view edition: poison first, then the
            # norm, so the guard detects exactly what the optimizer eats.
            # sum-of-psum-of-shard-squares == the replicated global norm
            # (the zero padding tail contributes zero).
            if self.fault_nan_step is not None:
                bad = step == self.fault_nan_step
                shard_grads = tuple(
                    jnp.where(bad, jnp.full(g.shape, jnp.nan, g.dtype), g)
                    for g in shard_grads
                )
            if self.health is not None:
                sq = sum(jnp.sum(jnp.square(g)) for g in shard_grads)
                metrics = {
                    **metrics,
                    "grad_norm": jnp.sqrt(jax.lax.psum(sq, "dp")),
                }
            i = _shard_index(jax.lax.axis_index("dp"))
            param_shards = layout.local_shards(params, i)
            opt_local = jax.tree.map(
                lambda x: x[0] if x.ndim == 2 else x, opt_state
            )
            upd, new_opt = self.tx.update(
                shard_grads, opt_local, param_shards
            )
            new_shards = optax.apply_updates(param_shards, upd)
            new_params = _gather_param_buckets(new_shards)
            new_opt = jax.tree.map(
                lambda x: x[None] if x.ndim == 1 else x, new_opt
            )
            new_res = tuple(r[None] for r in new_res) if lossy else ()
            return new_params, metrics, updates, new_res, new_opt

        sync = jax.shard_map(
            sync_body,
            mesh=self.mesh,
            in_specs=(
                param_specs, mstate_specs, P(BATCH_AXES), P(), P("dp"),
                opt_specs, P(),
            ),
            out_specs=(param_specs, P(), mstate_specs, P("dp"), opt_specs),
            check_vma=False,
        )

        def step_fn(state: TrainState, batch):
            rng = fold_in_step(state.rng, state.step)
            residual = state.grad_residual if lossy else ()
            new_params, metrics, updates, new_res, new_opt = sync(
                state.params, state.model_state, batch, rng, residual,
                state.opt_state, state.step,
            )
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                model_state=updates,
                grad_residual=new_res if lossy else None,
            )
            return new_state, metrics

        return step_fn

    def _plain_step_fn(self):
        def step_fn(state: TrainState, batch):
            rng = fold_in_step(state.rng, state.step)
            # Mixed precision: ONE compute copy per step, cast OUTSIDE
            # value_and_grad and differentiated directly — so fwd/bwd dots
            # AND the gradient leaves are compute-dtype (the partitioner's
            # grad all-reduce moves half the bytes), while the masters in
            # ``state.params`` are only touched by the fp32 update below.
            # Sits INSIDE the (possibly fused-scanned) body, so K-step
            # dispatch re-casts from the updated masters every step.
            # fp32 policy: returns state.params itself — identical trace.
            cparams = cast_to_compute(self.precision, state.params)

            if self.grad_accum > 1:
                # Microbatch scan: batch leading dim is split into
                # [accum, micro, ...]; grads accumulate in fp32. Replaces the
                # reference's host-side accumulation loop (BASELINE.json:9,
                # "DP + gradient accumulation") with an on-device lax.scan.
                def micro(carry, mb_and_idx):
                    mb, idx = mb_and_idx
                    grads_acc, metrics_acc, mstate = carry
                    (loss, (metrics, updates)), grads = jax.value_and_grad(
                        self._loss_and_updates, has_aux=True
                    )(cparams, mstate, mb, jax.random.fold_in(rng, idx), True)
                    grads_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                    )
                    metrics_acc = jax.tree.map(
                        lambda a, m: a + m.astype(jnp.float32), metrics_acc, metrics
                    )
                    return (grads_acc, metrics_acc, updates), None

                self._check_accum_divides(batch)
                mb0 = jax.tree.map(
                    lambda x: x.reshape((self.grad_accum, -1) + x.shape[1:]), batch
                )
                zeros_like_f32 = lambda t: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), t
                )
                abs_out = jax.eval_shape(
                    lambda: self._loss_and_updates(
                        cparams, state.model_state,
                        jax.tree.map(lambda x: x[0], mb0), rng, True,
                    )[1][0]
                )
                carry0 = (
                    zeros_like_f32(state.params),
                    zeros_like_f32(abs_out),
                    state.model_state,
                )
                (grads, metrics, updates), _ = jax.lax.scan(
                    micro, carry0, (mb0, jnp.arange(self.grad_accum))
                )
                grads = jax.tree.map(lambda g: g / self.grad_accum, grads)
                metrics = jax.tree.map(lambda m: m / self.grad_accum, metrics)
            else:
                (_, (metrics, updates)), grads = jax.value_and_grad(
                    self._loss_and_updates, has_aux=True
                )(cparams, state.model_state, batch, rng, True)

            # Grads -> fp32 AFTER the (partitioner-emitted) sync, BEFORE
            # instrumentation/clipping/update: poison, the guard's norm and
            # the optimizer all see fp32. No-op for fp32 policy and for the
            # grad_accum path (already accumulated fp32).
            grads = cast_grads_to_update(self.precision, grads)
            grads, metrics = self._instrument_grads(grads, state.step, metrics)
            updates_tx, new_opt_state = self._tx_update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates_tx)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                model_state=updates,
            )
            return new_state, metrics

        return step_fn

    def _step_fn(self):
        """(raw ``(state, batch) -> (state, metrics)`` step body, whether it
        must trace under the activation-mesh context). One selection point so
        the single-step and the fused K-step programs can never diverge: the
        fused path scans the SAME body."""
        # pipeline=False is the sequential parity-oracle mode — it must win
        # over the schedule (the engine would pipeline over pp regardless).
        if getattr(self.model, "schedule", None) == "1f1b_interleaved" and (
            getattr(self.model, "pipeline", True)
        ):
            fn, meshed = self._pipeline_step_fn(), True
        elif self._overlap:
            # Bucketed and/or sharded-update sync: owns every wire mode
            # (fp32 included) per bucket. Manual-mode shard_map body, like
            # the quantized path below.
            fn, meshed = self._overlapped_dp_step_fn(), False
        elif self.grad_comm != "fp32":
            # Manual-mode body (shard_map): ``sharding.constrain`` must stay
            # a no-op, so no MeshedJit (see _quantized_dp_step_fn).
            fn, meshed = self._quantized_dp_step_fn(), False
        else:
            fn, meshed = self._plain_step_fn(), True
        if self.health is not None:
            # Wrapping HERE — before the fused lax.scan — gives the
            # single-step and K-fused programs identical guard semantics.
            from .health import guard_step

            fn = guard_step(fn, self.health)
        return fn, meshed

    def _jit_step(self, fn, batch_shardings, meshed: bool):
        donate = (0,) if self._donate else ()
        jitted = jax.jit(
            fn,
            in_shardings=(self.state_shardings, batch_shardings),
            out_shardings=(self.state_shardings, None),
            donate_argnums=donate,
        )
        return MeshedJit(jitted, self.mesh) if meshed else jitted

    def _make_train_step(self):
        fn, meshed = self._step_fn()
        return self._jit_step(fn, batch_sharding(self.mesh), meshed)

    @property
    def train_step(self):
        if self._train_step is None:
            if self.state_shardings is None:
                raise RuntimeError("call Trainer.init() before train_step")
            self._train_step = self._make_train_step()
        return self._train_step

    def fused_train_step(self, steps_per_call: int):
        """K-step fused dispatch: ONE compiled program that ``lax.scan``s the
        train-step body over a stacked super-batch (leaves ``[K, B, ...]``,
        batch dim sharded — see ``sharding.super_batch_sharding`` /
        ``data.sharded_superbatches``). The host dispatches once per K steps,
        so per-step Python/dispatch overhead amortizes K-fold; per-step
        metrics come back stacked (leaves ``[K]``). The scanned body IS the
        single-step body (``_step_fn``), so grad_accum, quantized grad sync,
        ZeRO-1 and the pipeline schedule compose unchanged, and the per-step
        RNG stream (``fold_in_step`` of the carried ``state.step``) is
        identical to K unfused calls. ``steps_per_call=1`` returns
        ``train_step`` itself — bit-identical to today's loop by construction.
        """
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call={steps_per_call} must be >= 1")
        if steps_per_call == 1:
            return self.train_step
        if self._fused_step is None:
            if self.state_shardings is None:
                raise RuntimeError("call Trainer.init() before train_step")
            fn, meshed = self._step_fn()

            def fused_fn(state: TrainState, super_batch):
                return jax.lax.scan(fn, state, super_batch)

            from .sharding import super_batch_sharding

            # One wrapper serves every K: jit re-specializes on the
            # super-batch's leading dim like any other shape.
            self._fused_step = self._jit_step(
                fused_fn, super_batch_sharding(self.mesh), meshed
            )
        return self._fused_step

    @property
    def eval_step(self):
        if self._eval_step is None:
            if self.state_shardings is None:
                raise RuntimeError("call Trainer.init() before eval_step")

            def step_fn(state: TrainState, batch):
                _, (metrics, _) = self._loss_and_updates(
                    state.params, state.model_state, batch, state.rng, False
                )
                # Eval metrics leave the device fp32 regardless of the
                # model's compute dtype: evaluate() sums them across
                # batches, and a bf16 running sum loses integer resolution
                # past 256. Same-dtype cast is a trace-level no-op, so the
                # fp32 eval program is unchanged.
                return jax.tree.map(
                    lambda m: (
                        m.astype(jnp.float32)
                        if jnp.issubdtype(m.dtype, jnp.inexact) else m
                    ),
                    metrics,
                )

            self._eval_step = MeshedJit(
                jax.jit(
                    step_fn,
                    in_shardings=(self.state_shardings, batch_sharding(self.mesh)),
                ),
                self.mesh,
            )
        return self._eval_step


FAULT_KINDS = ("step", "nan", "hang", "corrupt")


class FaultSpec(NamedTuple):
    """One injected fault (docs/FAULT_TOLERANCE.md): ``kind`` is how the run
    breaks, ``step`` is the pre-step counter value it breaks at.

    - ``step``: hard-kill the process (os._exit — a crash, no cleanup);
    - ``nan``: poison that step's gradients on device (Trainer hook);
    - ``hang``: stall the host loop forever (heartbeat goes stale);
    - ``corrupt``: truncate the latest checkpoint, then hard-kill.
    """

    kind: str
    step: int


def parse_fault_injection(spec: str) -> FaultSpec | None:
    """'kind:K' -> FaultSpec(kind, K) for kind in FAULT_KINDS; '' -> None."""
    if not spec:
        return None
    kind, _, arg = spec.partition(":")
    if kind not in FAULT_KINDS or not arg.isdigit():
        raise ValueError(
            f"fault_injection {spec!r}: expected one of "
            f"{'|'.join(FAULT_KINDS)}:K"
        )
    return FaultSpec(kind, int(arg))


class Preempted(Exception):
    """Raised by :func:`fit` after a SIGTERM/SIGINT-triggered final save:
    the state at ``step`` is durable; the process should exit
    ``supervisor.EXIT_PREEMPTED`` without restarting."""

    def __init__(self, step: int, saved: bool):
        super().__init__(f"preempted at step {step} (saved={saved})")
        self.step = step
        self.saved = saved


class HealthRollback(Exception):
    """Raised by :func:`fit` when the health guard reports
    ``max_consecutive_anomalies`` anomalous steps in a row: the in-memory
    state is not worth continuing from — the caller (``cli.cmd_train``)
    restores the last durable checkpoint and re-enters training."""

    def __init__(self, step: int, consecutive: int):
        super().__init__(
            f"{consecutive} consecutive anomalous steps at step {step}"
        )
        self.step = step
        self.consecutive = consecutive


def evaluate(trainer: Trainer, state: TrainState, batches) -> dict[str, float]:
    """Run ``eval_step`` over an iterable of (sharded) batches and return the
    batch-mean of every metric. The vision tasks report top-1 ``accuracy``
    here — the parity half of the north-star metric (``BASELINE.json:2``:
    "top-1 parity at 90 epochs").

    Metric sums accumulate ON DEVICE and come back in ONE host transfer per
    pass: the old per-metric-per-batch ``float(v)`` drained the dispatch
    queue batches*metrics times, serializing eval on host round-trips.
    """
    import math

    sums = None
    count = 0
    for batch in batches:
        metrics = trainer.eval_step(state, batch)
        if sums is None:
            # fp32 accumulator regardless of the model's compute dtype
            # (eval_step already pins its outputs to fp32; this guards
            # custom/mocked eval steps too — jnp.add promotes to it).
            sums = jax.tree.map(
                lambda v: (
                    v.astype(jnp.float32)
                    if jnp.issubdtype(jnp.result_type(v), jnp.inexact) else v
                ),
                metrics,
            )
        else:
            sums = jax.tree.map(jnp.add, sums, metrics)
        count += 1
    if count == 0:
        raise ValueError("evaluate() got an empty batch iterable")
    sums = jax.device_get(sums)  # the pass's single D2H sync point
    out = {f"eval_{k}": float(v) / count for k, v in sums.items()}
    if "perplexity" in sums and "loss" in sums:
        # The standard eval number is exp(mean loss); a mean of per-batch
        # exp(loss) would overstate it (Jensen) and drift with batch count.
        out["eval_perplexity"] = math.exp(out["eval_loss"])
    return out


def check_fusion_cadences(
    steps_per_call: int,
    *,
    steps: int,
    start: int = 0,
    log_every: int = 0,
    eval_every: int = 0,
    save_every: int = 0,
    fault: FaultSpec | None = None,
) -> None:
    """Composition fences for fused multi-step dispatch: every host-side
    boundary (log/eval/save/fault/resume) must land on a fused-call edge,
    because the host only regains control every ``steps_per_call`` steps.
    Checked up front so a bad cadence fails by name, not as an off-by-K
    logging drift ten thousand steps in."""
    k = steps_per_call
    if k < 1:
        raise ValueError(f"steps_per_call={k} must be >= 1")
    if fault is not None and fault.kind not in FAULT_KINDS:
        raise ValueError(
            f"fault kind {fault.kind!r} not in {FAULT_KINDS}"
        )
    if k == 1:
        return
    for name, every in (
        ("steps", steps),
        ("log_every", log_every),
        ("eval_every", eval_every),
        ("save_every", save_every),
    ):
        if every and every % k:
            raise ValueError(
                f"steps_per_call={k} must divide {name}={every}: fused calls "
                f"advance {k} steps at a time, so every cadence boundary has "
                "to land on a call edge"
            )
    # nan:K is exempt: it fires ON DEVICE (the step body tests the carried
    # step counter), so it lands mid-scan just fine. The host-side kinds
    # (step/hang/corrupt) only get control at call edges.
    if fault is not None and fault.kind != "nan" and fault.step % k:
        raise ValueError(
            f"steps_per_call={k} must divide fault_step={fault.step} "
            f"(kind={fault.kind!r}): host-side fault injections fire between "
            "fused calls — use steps_per_call=1 for mid-interval faults"
        )
    if start % k:
        raise ValueError(
            f"resume step {start} is not a multiple of steps_per_call={k}: "
            "align save_every to the fused cadence (it is fenced above) or "
            "finish the partial interval with steps_per_call=1"
        )


def fit(
    trainer: Trainer,
    state: TrainState,
    batches,
    steps: int,
    log_every: int = 10,
    steps_per_call: int = 1,
    log_fn=print,
    writer=None,
    profiler=None,
    ckpt=None,
    save_every: int = 0,
    fault: FaultSpec | None = None,
    eval_every: int = 0,
    eval_fn=None,
    health=None,
    heartbeat_file: str | None = None,
    telemetry=None,
) -> tuple[TrainState, list[dict]]:
    """Host step loop.

    Resumes from ``state.step`` (callers align ``batches`` to the same
    index). Metrics are pulled to host only every ``log_every`` steps, and
    asynchronously (``metrics.DeferredMetrics``): a log boundary STARTS a
    D2H copy and emits the PREVIOUS boundary's already-arrived values — one
    interval of lag, zero dispatch-queue drains for observability (the
    final interval flushes before return, so history is always complete).
    Checkpoint saves are async and off the loop. Loop-status events (fault
    injections, preemption saves, rollbacks) flow through the SAME emit
    path as metric lines (``metrics.event_record``), so history, log_fn and
    the supervisor's stdout parse all see one ordered stream.

    ``steps_per_call`` = K > 1 fuses K steps into one on-device scan
    (:meth:`Trainer.fused_train_step`): ``batches`` must then yield stacked
    super-batches (leaves ``[K, B, ...]`` — ``data.sharded_superbatches``),
    and K must divide ``steps`` and every log/eval/save/fault cadence
    (:func:`check_fusion_cadences`; on-device ``nan:K`` is exempt). K=1 is
    bit-identical to the unfused loop — it IS the unfused loop.

    ``eval_every`` > 0 runs :func:`evaluate` over ``eval_fn()`` (a callable
    returning a fresh iterable of sharded eval batches) every that many
    steps and after the final step; eval metrics join the history/TB stream
    prefixed ``eval_``.

    Resilience (docs/FAULT_TOLERANCE.md):

    - ``fault`` injects one deterministic failure (:class:`FaultSpec`):
      ``step``/``corrupt`` hard-kill via ``os._exit(EXIT_FAULT)`` (crash
      semantics — no atexit, no async-save drain; ``corrupt`` first
      truncates the latest checkpoint), ``hang`` stalls the loop forever,
      ``nan`` is compiled into the step body (Trainer ``fault_nan_step``).
    - SIGTERM/SIGINT (preemption) is converted into a final SYNCHRONOUS
      ``ckpt.save(force=True) + wait()`` at the next call edge, then
      :class:`Preempted` — resume loses zero durable steps.
    - The loop touches ``heartbeat_file`` (default: ``$DDL_HEARTBEAT_FILE``,
      exported by the supervisor) at loop and log boundaries; the log-
      boundary touch follows a real D2H sync, so a hung device stops the
      heartbeat within one logging interval.
    - ``health`` (a ``config.HealthConfig``): when the logged metric stream
      reports ``max_consecutive_anomalies`` consecutive anomalous steps
      (detection lags one logging interval — the deferred-fetch contract),
      raises :class:`HealthRollback` for the caller's restore-and-retry.

    Telemetry (``telemetry.Telemetry``; docs/OBSERVABILITY.md): when an
    enabled bundle is passed, the loop opens host-side spans (``step`` >
    ``data_wait``/``dispatch``/``device_wait``, plus ``checkpoint`` and
    ``eval``), attributes wall time to the goodput ledger (productive vs
    compile / data wait / checkpoint stall / eval / rollback replay — the
    first cold dispatch, which compiles inside the call, is classified
    ``compile`` and registered in the device registry), and dumps a
    flight record on every fault / rollback / preemption path. Disabled
    (the default None) costs one
    truthiness check per hook. Heartbeat touches carry ``{step, attempt,
    phase}`` so the supervisor's hang kill can say WHERE the child hung.
    """
    import os
    import signal
    import sys

    from .metrics import DeferredMetrics, event_record
    from .supervisor import ATTEMPT_ENV, EXIT_FAULT, HEARTBEAT_ENV
    from .supervisor import touch as hb_touch
    from .telemetry import NULL_TELEMETRY

    if eval_every and eval_fn is None:
        raise ValueError("eval_every > 0 requires eval_fn")
    k = steps_per_call
    start = int(state.step)
    check_fusion_cadences(
        k, steps=steps, start=start, log_every=log_every,
        eval_every=eval_every, save_every=save_every, fault=fault,
    )
    if fault is not None and fault.kind == "corrupt" and ckpt is None:
        raise ValueError("fault_injection=corrupt:K requires a checkpoint_dir")
    step_call = trainer.train_step if k == 1 else trainer.fused_train_step(k)

    hb = (
        heartbeat_file if heartbeat_file is not None
        else os.environ.get(HEARTBEAT_ENV)
    )
    max_consec = (
        health.max_consecutive_anomalies if health is not None else 0
    )
    tel = telemetry if telemetry is not None else NULL_TELEMETRY
    if tel.enabled:
        # One clock: every span is also an event on the host plane of any
        # jax profile taken meanwhile (train.profile_steps), beside the
        # device ops.
        tel.tracer.annotate = jax.profiler.TraceAnnotation
    ledger = tel.ledger
    attempt = int(os.environ.get(ATTEMPT_ENV, "0") or 0)

    def beat(phase, at):
        # Content-bearing heartbeat: mtime still advances (the hang
        # detector's change signal), and the supervisor can now report
        # the last {step, attempt, phase} the loop reached.
        hb_touch(hb, step=at, attempt=attempt, phase=phase)

    history = []

    def emit(m):
        history.append(m)
        log_fn(m)
        tel.note_event(m)
        if writer is not None and "event" not in m:
            writer.write(m["step"], {x: v for x, v in m.items() if x != "step"})
        if max_consec and m.get("consecutive_anomalies", 0) >= max_consec:
            raise HealthRollback(
                int(m.get("step", 0)), int(m["consecutive_anomalies"])
            )

    deferred = DeferredMetrics(emit)

    def run_eval(end):
        # evaluate() is a sync point anyway; draining the deferred log first
        # keeps the train line for step N ahead of its eval line.
        deferred.flush()
        t_ev = time.perf_counter()
        with tel.span("eval", step=end):
            m = evaluate(trainer, state, eval_fn())
        if ledger is not None:
            ledger.add("eval", time.perf_counter() - t_ev)
        m["step"] = end
        emit(m)

    preempt = {"signum": None}

    def _on_preempt(signum, frame):
        preempt["signum"] = signum

    prev_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev_handlers[sig] = signal.signal(sig, _on_preempt)
        except ValueError:
            pass  # not the main thread (a test harness driving fit)

    t0 = time.perf_counter()
    it = iter(batches)
    end = start
    beat("start", start)
    try:
        for i in range(start, steps, k):
            if preempt["signum"] is not None:
                # Preemption-safe save: synchronous, force (off-cadence
                # steps must still save), before the exception — by the
                # time Preempted propagates, the state IS durable.
                saved = False
                if ckpt is not None:
                    with tel.span("checkpoint", step=end, forced=True):
                        if ckpt.latest_step() != end:
                            ckpt.save(
                                end, state, {"next_index": end}, force=True
                            )
                        ckpt.wait()
                    saved = True
                deferred.emit_event(event_record(
                    "preempt_save", end, saved=saved,
                    signum=int(preempt["signum"]),
                ))
                tel.flight_dump(
                    "preempt", step=end, phase="preempt",
                    signum=int(preempt["signum"]), saved=saved,
                )
                tel.write_trace()
                sys.stdout.flush()
                raise Preempted(end, saved)
            if fault is not None and i == fault.step and fault.kind != "nan":
                # Injected faults exit via os._exit (or never return), so
                # the caller's finally can't run: the attempt's ledger
                # record and flight/trace files are written HERE or lost.
                if fault.kind == "step":
                    deferred.emit_event(event_record("fault_kill", i))
                    tel.flight_dump("fault_kill", step=i, phase="fault")
                    tel.write_trace()
                    if ledger is not None:
                        ledger.close(i)
                    sys.stdout.flush()
                    os._exit(EXIT_FAULT)
                if fault.kind == "hang":
                    deferred.emit_event(event_record("fault_hang", i))
                    # Dump BEFORE the stall: the supervisor's recovery is
                    # SIGKILL, after which this process writes nothing.
                    tel.flight_dump("fault_hang", step=i, phase="fault")
                    tel.write_trace()
                    if ledger is not None:
                        ledger.close(i)
                    sys.stdout.flush()
                    while True:  # heartbeat stale -> supervisor SIGKILLs
                        time.sleep(3600)
                if fault.kind == "corrupt":
                    ckpt.wait()  # corrupt a FINALIZED latest, not a temp dir
                    bad = ckpt.corrupt_latest_for_test()
                    deferred.emit_event(event_record(
                        "fault_corrupt", i, corrupted_step=bad
                    ))
                    tel.flight_dump("fault_corrupt", step=i, phase="fault")
                    tel.write_trace()
                    if ledger is not None:
                        ledger.close(i)
                    sys.stdout.flush()
                    os._exit(EXIT_FAULT)
            beat("step", end)
            stop = False
            with tel.span("step", step=i):
                t_dw = time.perf_counter()
                try:
                    with tel.span("data_wait", step=i):
                        batch = next(it)
                except StopIteration:
                    stop = True
                if not stop:
                    if ledger is not None:
                        ledger.add(
                            "data_wait", time.perf_counter() - t_dw
                        )
                    # The first dispatch in this process traces + compiles
                    # inside the call (the AOT .lower().compile() path
                    # would NOT seed the traced-call cache on this jax —
                    # it costs a full SECOND compile), so the honest
                    # accounting is: classify the whole first dispatch as
                    # "compile" and register the executable without a
                    # memory probe (tools/telemetry_report.py owns that
                    # probe and its extra compile). Registry presence
                    # doubles as the warm-cache marker, so a health-
                    # rollback re-entry goes back to step accounting.
                    step_name = (
                        "train_step" if k == 1 else f"fused_train_step_{k}"
                    )
                    cold = tel.enabled and step_name not in tel.registry
                    t_disp = time.perf_counter()
                    with tel.span("dispatch", step=i):
                        state, metrics = step_call(state, batch)
                    dt_disp = time.perf_counter() - t_disp
                    if cold:
                        tel.record_exe(
                            step_name, None, compile_s=dt_disp,
                            donated_args=1,
                        )
                        if ledger is not None:
                            ledger.add("compile", dt_disp)
                    elif ledger is not None:
                        # productive vs rollback_replay: re-earning ground
                        # a prior attempt already covered is not goodput.
                        ledger.step_time(dt_disp, i + k)
            if stop:
                break
            end = i + k
            if profiler is not None:
                # Per-step granularity for the window bounds; under fusion
                # the trace start/stop still only take effect at call edges.
                for j in range(i, end):
                    profiler.step(j)
            if log_every and end % log_every == 0:
                # Fused metrics come back stacked [K]; the logged step is
                # the interval's last, same as the unfused loop.
                last = (
                    metrics if k == 1
                    else jax.tree.map(lambda v: v[-1], metrics)
                )
                # push materializes the PREVIOUS interval — a real D2H
                # sync — which is exactly what the device_wait span times.
                with tel.span("device_wait", step=end):
                    deferred.push(
                        end, last, wall_s=round(time.perf_counter() - t0, 3)
                    )
                # ... so this touch is the honest device-liveness signal.
                beat("log", end)
            if eval_every and end % eval_every == 0:
                run_eval(end)
                beat("eval", end)
            if ckpt is not None and save_every and end % save_every == 0:
                t_ck = time.perf_counter()
                with tel.span("checkpoint", step=end):
                    ckpt.save(end, state, {"next_index": end})
                    if fault is not None:
                        # Fault injection simulates a crash at an arbitrary
                        # step; the recovery contract is "resume from the
                        # last DURABLE save". Draining here makes every
                        # completed save durable, so crash→resume is
                        # deterministic instead of racing the async writer
                        # (ADVICE.md r1).
                        ckpt.wait()
                if ledger is not None:
                    ledger.add(
                        "checkpoint_stall", time.perf_counter() - t_ck
                    )
                beat("save", end)
        if eval_every and end % eval_every != 0 and end > start:
            run_eval(end)  # final eval so short runs still report one
        deferred.flush()
    except HealthRollback as rb:
        # The pending interval describes state that is being rewound;
        # materializing it could re-trigger the policy mid-unwind.
        deferred.discard()
        emit(event_record(
            "health_rollback", rb.step, consecutive=rb.consecutive
        ))
        tel.flight_dump(
            "health_rollback", step=rb.step, phase="rollback",
            consecutive=rb.consecutive,
        )
        tel.write_trace()
        sys.stdout.flush()
        raise
    finally:
        for sig, handler in prev_handlers.items():
            signal.signal(sig, handler)
        if profiler is not None:
            profiler.close()
        if writer is not None:
            writer.flush()
    return state, history
