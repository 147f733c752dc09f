"""Logical-axis partitioning rules — how every array picks up its sharding.

The reference binds each parallelism strategy to hand-managed NCCL groups and
per-rank tensor slices; here a *logical axis name* is attached to each array
dimension at model-definition time (via ``flax.linen.with_logical_partitioning``
/ ``with_logical_constraint``) and ONE rules table maps logical names to mesh
axes. Changing parallelism strategy = changing the rules/mesh, never the model.

Logical axis vocabulary used across the model zoo:

==========  =====================================================
``batch``    global batch dimension (activations, inputs)
``seq``      sequence/token dimension (activations)
``embed``    model/hidden dimension
``heads``    attention heads
``kv``       per-head dimension
``mlp``      MLP hidden (intermediate) dimension
``vocab``    vocabulary / classifier output dimension
``expert``   MoE expert dimension
``stage``    pipeline-stage-stacked parameters
``conv_*``   conv kernel spatial/channel dims (never sharded)
==========  =====================================================
"""

from __future__ import annotations

import contextlib
import contextvars
import warnings

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .mesh import BATCH_AXES

# Rules: logical axis -> mesh axis (or tuple of axes, or None = replicated).
# Megatron-style TP shards heads/mlp/vocab over 'tp'; FSDP shards the embed
# dimension of parameters over 'fsdp'; batch is sharded jointly over dp+fsdp;
# seq over 'cp' (ring/Ulysses context parallelism); experts over 'ep'.
DEFAULT_LOGICAL_RULES: tuple[tuple[str, str | tuple[str, ...] | None], ...] = (
    ("batch", BATCH_AXES),
    ("seq", "cp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("stage", "pp"),
    ("pos", None),
    # Pipelined models' embedding/LM-head vocab dim: sharded over pp (on top
    # of tp) so the table is NOT replicated per pipeline stage — each pp rank
    # stores vocab/(tp*pp); XLA partitions the lookup gather and the tied
    # attend matmul in the auto region (no pipeline involvement).
    ("vocab_pp", ("tp", "pp")),
    # Inside-attention layout for Ulysses sequence parallelism: heads pick up
    # the cp axis (on top of tp) while seq is gathered; constraining q/k/v to
    # these makes the SPMD partitioner emit the seq<->heads all-to-alls.
    ("seq_attn", None),
    ("heads_attn", ("tp", "cp")),
    ("conv_h", None),
    ("conv_w", None),
    ("conv_in", None),
    ("norm", None),
)


def make_rules(
    **overrides: str | tuple[str, ...] | None,
) -> tuple[tuple[str, str | tuple[str, ...] | None], ...]:
    """DEFAULT_LOGICAL_RULES with per-logical-axis overrides.

    e.g. ``make_rules(embed=None)`` disables FSDP parameter sharding.
    """
    table = dict(DEFAULT_LOGICAL_RULES)
    for k, v in overrides.items():
        table[k] = v
    return tuple(table.items())


def logical_to_mesh_sharding(tree, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES):
    """Map a pytree of logical-axis-annotated metadata (as produced by
    ``nn.get_partition_spec`` on a flax variable tree) to ``NamedSharding``s.
    """
    return nn.logical_to_mesh_sharding(tree, mesh, rules)


def named_sharding(mesh: Mesh, *axes) -> NamedSharding:
    """``NamedSharding(mesh, P(*axes))`` shorthand."""
    return NamedSharding(mesh, P(*axes))


def batch_spec() -> P:
    """PartitionSpec for a [batch, ...] array: batch over dp+fsdp."""
    return P(BATCH_AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def super_batch_spec() -> P:
    """PartitionSpec for a [steps_per_call, batch, ...] stacked super-batch
    (fused K-step dispatch): the scan dim is replicated — every member runs
    all K steps — and the batch dim shards exactly as a plain batch."""
    return P(None, BATCH_AXES)


def super_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, super_batch_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def leading_dim_shardings(abs_tree, mesh: Mesh, axis: str = "dp"):
    """NamedShardings for per-member flat state: any leaf whose LEADING
    dimension equals ``mesh.shape[axis]`` is sharded over ``axis`` on that
    dimension; everything else (step counters, scalars) is replicated.

    This is the layout of the flat-shard optimizer state under
    ``train.update_sharding='sharded'`` (``comms_overlap.BucketLayout.
    stacked_shards``: row ``i`` of a ``[n, shard]`` leaf is member ``i``'s
    shard) and of the per-bucket error-feedback residuals — state that is
    per-member by construction, where replication would both waste HBM and
    be semantically wrong.
    """
    n = mesh.shape[axis]

    def one(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] == n:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, abs_tree)


# The mesh activation constraints resolve against. A package-local contextvar
# (entered via ``activation_mesh``) rather than ``jax.sharding.set_mesh``:
# flax's ``scope.param`` shape-validates every apply by eval_shape'ing the
# init_fn, and DenseGeneral's init builds kernels flat-rank-2 before
# reshaping — under a *global* mesh context the boxed rank-3 logical
# constraint is applied to that flat value and tracing fails. Passing the
# mesh explicitly into ``nn.with_logical_constraint`` sidesteps flax's
# global-mesh path entirely while making the constraint just as real.
_MESH_CTX: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "ddl_activation_mesh", default=None
)


@contextlib.contextmanager
def activation_mesh(mesh: Mesh):
    """Make ``constrain()`` resolve against ``mesh`` inside this context.

    The Trainer enters this around every trace/compile/execute of its steps —
    without it every activation-level constraint in the models is a silent
    no-op (the round-2 Ulysses/Megatron-SP failure mode)."""
    token = _MESH_CTX.set(mesh)
    try:
        yield
    finally:
        _MESH_CTX.reset(token)


def _rule_axes(rules_table: dict, name) -> tuple[str, ...]:
    entry = rules_table.get(name)
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def validate_logical_spec(
    logical_names, shape, rules, mesh: Mesh, *, what: str = "array"
) -> None:
    """Validate one array's logical annotation against a rules table + mesh.

    Raises ``ValueError`` when the rules map two dims of the same array onto
    one mesh axis (flax silently DROPS the colliding rule — round 2 showed
    how silently-weaker sharding survives parity tests); warns loudly when a
    sharded dim is not divisible by its mesh-axis product (XLA pads —
    correct but wasteful, and byte accounting drifts). Checked
    property-style across every legal mesh × zoo model in
    ``tests/test_sharding_properties.py``.
    """
    table = dict(rules)
    seen: dict[str, object] = {}
    for dim, name in enumerate(logical_names):
        axes = _rule_axes(table, name)
        for axis in axes:
            if axis not in mesh.shape:
                raise ValueError(
                    f"{what}: logical axis {name!r} maps to unknown mesh "
                    f"axis {axis!r}"
                )
            if axis in seen and mesh.shape[axis] > 1:
                raise ValueError(
                    f"{what}: mesh axis {axis!r} assigned to two dims "
                    f"(logical {seen[axis]!r} and {name!r}) — flax would "
                    "silently drop one"
                )
            seen[axis] = name
        ways = 1
        for axis in axes:
            ways *= mesh.shape[axis]
        if ways > 1 and shape[dim] % ways:
            # Warning, not error: XLA pads uneven shards correctly (an odd
            # vocab like GPT-2's 50257 over tp/pp is routine); the cost is
            # wasted HBM/compute on the padding and byte-accounting drift,
            # which deserves a loud signal but must not block training.
            warnings.warn(
                f"{what}: dim {dim} (logical {name!r}, size {shape[dim]}) "
                f"not divisible by its {ways}-way sharding — XLA will pad",
                RuntimeWarning,
                stacklevel=2,
            )


def validate_tree_shardings(abs_tree, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES):
    """Run :func:`validate_logical_spec` over every ``nn.Partitioned`` leaf
    of an abstract (eval_shape'd) variable tree."""
    def check(path, leaf):
        if isinstance(leaf, nn.Partitioned):
            validate_logical_spec(
                leaf.names, leaf.value.shape, rules, mesh,
                what=jax.tree_util.keystr(path),
            )
        return leaf

    jax.tree_util.tree_map_with_path(
        check, abs_tree, is_leaf=lambda l: isinstance(l, nn.Partitioned)
    )


def constrain(x, *logical_axes, rules=None):
    """Constrain an activation's sharding by logical axis names (no-op outside
    any mesh context and inside ``shard_map``). Used inside model code
    between blocks.

    Rules resolution: an ambient ``nn.logical_axis_rules(...)`` context (the
    Trainer installs its own rules around every model call) takes precedence;
    otherwise ``DEFAULT_LOGICAL_RULES``. This is what lets a rules preset like
    Megatron sequence parallelism reach activation constraints, not only
    parameter shardings."""
    # Inside a shard_map body the mesh axes are Manual: the body already
    # holds its per-device shard, and with_sharding_constraint refuses a
    # spec that names a Manual axis.
    if jax.sharding.get_abstract_mesh().manual_axes:
        return x
    if rules is None:
        rules = nn.get_logical_axis_rules() or DEFAULT_LOGICAL_RULES
    mesh = _MESH_CTX.get()
    return nn.with_logical_constraint(x, P(*logical_axes), rules=rules, mesh=mesh)
