"""Config/Trainer-build-time fences for unsupported strategy pairs.

VERDICT r4 Missing #4: SURVEY §2b claims "all strategies compose through
one mechanism"; the corners where that is false (the interleaved pipeline
engine owns its own differentiation, so pp x ep and pp x cp do not
compose) must fail AT BUILD TIME with an error naming the composition —
and a mesh axis no model component consumes (pp without a pipelined
model, ep without experts) must fail rather than silently replicate.
"""

import pytest

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.train import Trainer, get_task, make_optimizer

from helpers import mesh_of


def _trainer(model, mesh):
    return Trainer(model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh)


@pytest.mark.parametrize("axis", ["ep", "cp"])
def test_pipeline_rejects_ep_and_cp(axis):
    mesh = mesh_of(dp=2, pp=2, **{axis: 2})
    model = models.get_model(
        "gpt2_pp", size="tiny", vocab_size=64, max_len=32,
        num_stages=2, num_microbatches=2, mesh=mesh,
    )
    with pytest.raises(NotImplementedError, match=f"pipeline x .*{axis}"):
        _trainer(model, mesh)


def test_pp_axis_without_pipelined_model_is_rejected():
    mesh = mesh_of(dp=2, pp=2)
    model = models.get_model("gpt2", size="tiny", vocab_size=64, max_len=32)
    with pytest.raises(ValueError, match="not pipelined"):
        _trainer(model, mesh)


def test_ep_axis_without_moe_model_is_rejected():
    mesh = mesh_of(dp=2, ep=2)
    model = models.get_model("gpt2", size="tiny", vocab_size=64, max_len=32)
    with pytest.raises(ValueError, match="no experts"):
        _trainer(model, mesh)


def test_config_path_hits_the_fence():
    # The same fence through build_all (the user-facing path): the shipped
    # pipelined config with an ep override must fail by name, not train a
    # silently-degenerate program.
    import os

    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = apply_overrides(
        load_config(os.path.join(repo, "configs", "gpt2_pp.py")),
        ["model.kwargs.size=tiny", "model.kwargs.max_len=32",
         "model.kwargs.vocab_size=128", "data.batch_size=8",
         "data.seq_len=16", "data.vocab_size=128",
         "mesh.dp=2", "mesh.pp=2", "mesh.ep=2",
         "model.kwargs.num_stages=2", "model.kwargs.num_microbatches=2"],
    )
    with pytest.raises(NotImplementedError, match="pipeline x .*ep"):
        build_all(cfg)


def test_cp_axis_without_cp_attention_is_rejected():
    mesh = mesh_of(dp=2, cp=2)
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, attn_impl="xla"
    )
    with pytest.raises(ValueError, match="not context-parallel"):
        _trainer(model, mesh)


def test_allow_idle_axes_escape_hatch():
    # The HLO control harness legitimately idles an axis; the escape must
    # keep that path building.
    mesh = mesh_of(dp=2, cp=2)
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, attn_impl="xla"
    )
    Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        allow_idle_axes=True,
    )


# ---------------------------------------------------------------------------
# Mixed precision (train.precision) x everything else
#
# The matrix the docs promise (docs/MIXED_PRECISION.md): legal pairs must
# BUILD (their numerics are pinned in test_precision.py), illegal pairs must
# fail at config/Trainer-build time with an error naming the pair and the
# way out.
# ---------------------------------------------------------------------------


def _bf16_model(**kw):
    import jax.numpy as jnp

    return models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0,
        dtype=jnp.bfloat16, **kw,
    )


def _precision_trainer(model, mesh, precision="bf16", optim="adamw", **kw):
    return Trainer(
        model, make_optimizer(optim, 1e-3, precision=precision),
        get_task("lm"), mesh, donate=False, precision=precision, **kw,
    )


@pytest.mark.parametrize(
    "trainer_kw",
    [
        dict(grad_comm="int8"),
        dict(grad_comm="bf16"),
        dict(zero1=True),
        dict(grad_accum=2),
        dict(fault_nan_step=1),
    ],
    ids=["grad_comm-int8", "grad_comm-bf16", "zero1", "grad_accum",
         "fault-injection"],
)
def test_precision_legal_pairs_build(trainer_kw):
    _precision_trainer(_bf16_model(), mesh_of(dp=8), **trainer_kw)


def test_precision_composes_with_health_guard():
    from distributeddeeplearning_tpu.config import HealthConfig

    _precision_trainer(
        _bf16_model(), mesh_of(dp=8), health=HealthConfig(enabled=True)
    )


def test_precision_composes_with_remat():
    _precision_trainer(_bf16_model(remat="full"), mesh_of(dp=8))


def test_precision_rejects_pipelined_model():
    mesh = mesh_of(dp=2, pp=2)
    model = models.get_model(
        "gpt2_pp", size="tiny", vocab_size=64, max_len=32,
        num_stages=2, num_microbatches=2, mesh=mesh,
    )
    with pytest.raises(NotImplementedError, match="pipelined"):
        _precision_trainer(model, mesh)


def test_precision_rejects_model_dtype_mismatch():
    # fp32 model + bf16 policy: the compute cast would silently do nothing
    # the model honors — fail with the route (policy owns the dtype).
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0
    )
    with pytest.raises(ValueError, match="model.dtype"):
        _precision_trainer(model, mesh_of(dp=8))


@pytest.mark.parametrize(
    "optim, match",
    [
        ("sgd", "optim.name='sgd'"),
        ("adamw_fused", "adamw_fused"),
    ],
)
def test_bf16_full_rejects_non_adamw_moments(optim, match):
    with pytest.raises(ValueError, match=match):
        make_optimizer(optim, 1e-3, precision="bf16_full")


def test_bf16_policy_keeps_fused_adamw():
    # Only bf16_full touches moment storage; plain bf16 must not lose the
    # fused-kernel path.
    make_optimizer("adamw_fused", 1e-3, precision="bf16")


def test_unknown_policy_fails_by_name():
    with pytest.raises(ValueError, match="train.precision.policy"):
        make_optimizer("adamw", 1e-3, precision="fp8")


def test_precision_config_block_rejects_scalar_override():
    # `train.precision=bf16` is a likely typo for `.policy=` — it must not
    # silently replace the block.
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = load_config("configs/gpt2_owt.py")
    with pytest.raises(
        ValueError, match=r"train\.precision is a config block"
    ):
        apply_overrides(cfg, ["train.precision=bf16"])


def test_config_path_rejects_dtype_policy_conflict():
    # gpt2_owt ships the legacy model.kwargs.dtype='bfloat16'; asking for a
    # CONFLICTING policy through build_all must fail with the route out.
    import os

    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = apply_overrides(
        load_config(os.path.join(repo, "configs", "gpt2_owt.py")),
        ['model.kwargs={"size":"tiny","max_len":32,"vocab_size":128,'
         '"dtype":"float32"}',
         "data.batch_size=8", "data.seq_len=16", "data.vocab_size=128",
         "train.precision.policy=bf16", "optim.name=adamw"],
    )
    with pytest.raises(ValueError, match="the policy owns the compute dtype"):
        build_all(cfg)


# ---------------------------------------------------------------------------
# Overlapped gradient sync / sharded weight update (train.grad_bucket_mb,
# train.update_sharding) x everything else
#
# The matrix docs/OVERLAP.md promises: both knobs are pure-DP v1 features —
# the pairs they cannot serve must fail at build time naming the knob, the
# pairs they can (zero1, lossy wire, precision policies, health guard) must
# build (their numerics are pinned in test_overlap.py).
# ---------------------------------------------------------------------------


def _overlap_trainer(mesh, model=None, optim="adamw", **kw):
    if model is None:
        model = models.get_model(
            "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0
        )
    return Trainer(
        model, make_optimizer(optim, 1e-3), get_task("lm"), mesh,
        donate=False, **kw,
    )


@pytest.mark.parametrize(
    "knob", [dict(grad_bucket_mb=1.0), dict(update_sharding="sharded")],
    ids=["bucketed", "sharded"],
)
def test_overlap_rejects_pipelined_model(knob):
    mesh = mesh_of(dp=2, pp=2)
    model = models.get_model(
        "gpt2_pp", size="tiny", vocab_size=64, max_len=32,
        num_stages=2, num_microbatches=2, mesh=mesh,
    )
    name = next(iter(knob))
    with pytest.raises(NotImplementedError, match=f"{name}.*pipelined"):
        _overlap_trainer(mesh, model=model, **knob)


@pytest.mark.parametrize(
    "knob", [dict(grad_bucket_mb=1.0), dict(update_sharding="sharded")],
    ids=["bucketed", "sharded"],
)
def test_overlap_rejects_busy_model_axes(knob):
    mesh = mesh_of(dp=4, fsdp=2)
    with pytest.raises(NotImplementedError, match="pure-DP"):
        _overlap_trainer(mesh, **knob)


def test_overlap_rejects_grad_accum():
    with pytest.raises(NotImplementedError, match="grad_bucket_mb.*grad_accum"):
        _overlap_trainer(mesh_of(dp=8), grad_bucket_mb=1.0, grad_accum=2)


def test_overlap_rejects_bad_mode_and_negative_bucket():
    with pytest.raises(ValueError, match="update_sharding"):
        _overlap_trainer(mesh_of(dp=8), update_sharding="zero3")
    with pytest.raises(ValueError, match="grad_bucket_mb"):
        _overlap_trainer(mesh_of(dp=8), grad_bucket_mb=-0.5)


def test_sharded_setup_rejects_fused_adamw_state():
    # Direct-Trainer users bypass the cli config fence; the optimizer STATE
    # type at setup is the Trainer's first sight of the fused kernel.
    from distributeddeeplearning_tpu import data as data_lib

    tr = _overlap_trainer(
        mesh_of(dp=8), optim="adamw_fused", update_sharding="sharded"
    )
    ds = data_lib.SyntheticTokens(
        batch_size=8, seq_len=16, vocab_size=64, seed=0, n_distinct=4
    )
    with pytest.raises(NotImplementedError, match="adamw_fused"):
        tr.setup(ds.batch(0))


@pytest.mark.parametrize(
    "extra_overrides, match",
    [
        ([], "adamw_fused"),
        (["optim.name=adamw"], "weight_decay"),
        (["optim.name=adamw", "optim.weight_decay=0.0"], "grad_clip"),
    ],
    ids=["fused-kernel", "weight-decay", "grad-clip"],
)
def test_cli_fences_sharded_update_by_optimizer_feature(extra_overrides, match):
    # gpt2_owt ships adamw_fused + weight_decay + grad_clip — peeling them
    # off one override at a time must hit each fence by name.
    import os

    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = apply_overrides(
        load_config(os.path.join(repo, "configs", "gpt2_owt.py")),
        ["train.update_sharding=sharded"] + extra_overrides,
    )
    with pytest.raises(NotImplementedError, match=match):
        build_all(cfg)


def test_cli_threads_overlap_knobs():
    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, OptimConfig, TrainConfig,
    )

    cfg = Config(
        model=ModelConfig(
            name="gpt2",
            kwargs=dict(size="tiny", vocab_size=128, max_len=32,
                        dropout_rate=0.0),
        ),
        data=DataConfig(kind="synthetic_tokens", batch_size=8, seq_len=16,
                        vocab_size=128),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(steps=1, task="lm", update_sharding="sharded",
                          grad_bucket_mb=0.25),
        mesh=MeshConfig(dp=-1),
    )
    _, _, trainer, _ = build_all(cfg)
    assert trainer.update_sharding == "sharded"
    assert trainer.grad_bucket_mb == 0.25


@pytest.mark.parametrize(
    "trainer_kw",
    [
        dict(update_sharding="sharded", zero1=True),
        dict(update_sharding="sharded", grad_comm="int8"),
        dict(grad_bucket_mb=0.5, grad_comm="bf16"),
        dict(grad_bucket_mb=0.5, fault_nan_step=1),
    ],
    ids=["sharded-zero1", "sharded-int8", "bucketed-bf16",
         "bucketed-fault-injection"],
)
def test_overlap_legal_pairs_build(trainer_kw):
    _overlap_trainer(mesh_of(dp=8), **trainer_kw)


def test_overlap_composes_with_precision_policy():
    _precision_trainer(
        _bf16_model(), mesh_of(dp=8), update_sharding="sharded"
    )
    _precision_trainer(_bf16_model(), mesh_of(dp=8), grad_bucket_mb=0.5)


def test_overlap_composes_with_health_guard():
    from distributeddeeplearning_tpu.config import HealthConfig

    _overlap_trainer(
        mesh_of(dp=8), update_sharding="sharded",
        health=HealthConfig(enabled=True),
    )


# ---------------------------------------------------------------------------
# Hierarchical ICI+DCN gradient sync (train.comm_hierarchy, mesh.dcn_dp)
# x everything else
#
# The matrix docs/MULTISLICE.md promises: the hierarchy rides the overlapped
# step path, so it inherits the pure-DP fences above; its own fences are
# topology-shaped (mode names, dcn_dp divisibility, degenerate slices).
# Legal pairs build here; their numerics are pinned in test_hier.py.
# ---------------------------------------------------------------------------


def test_hierarchy_rejects_unknown_mode():
    with pytest.raises(ValueError, match="comm_hierarchy"):
        _overlap_trainer(mesh_of(dp=8), dcn_dp=2, comm_hierarchy="fastest")


def test_hierarchy_rejects_forced_on_single_slice():
    # comm_hierarchy='hierarchical' with dcn_dp=1 has no cross-slice axis to
    # decompose over — a silent flat fallback would misreport the telemetry.
    with pytest.raises(ValueError, match="dcn_dp"):
        _overlap_trainer(mesh_of(dp=8), dcn_dp=1, comm_hierarchy="hierarchical")


def test_hierarchy_rejects_indivisible_and_degenerate_topology():
    from distributeddeeplearning_tpu.comms_hier import (
        check_comm_hierarchy_config,
    )

    # dp=8 over dcn_dp=3 slices: no even split.
    with pytest.raises(ValueError, match="divisible"):
        check_comm_hierarchy_config(
            comm_hierarchy="hierarchical", dcn_dp=3, dp=8
        )
    # dp == dcn_dp: every "slice" is one member — ici degenerates to 1 and
    # the intra phases are no-ops; flat IS the hierarchy, so refuse.
    with pytest.raises(ValueError, match="ici"):
        check_comm_hierarchy_config(
            comm_hierarchy="hierarchical", dcn_dp=8, dp=8
        )


def test_hierarchy_inherits_pure_dp_fences():
    # Hierarchy routes through the overlapped step path, so busy model axes
    # and grad_accum must fail by name exactly like grad_bucket_mb does.
    with pytest.raises(NotImplementedError, match="pure-DP"):
        _overlap_trainer(
            mesh_of(dp=4, fsdp=2), dcn_dp=2, comm_hierarchy="hierarchical"
        )
    with pytest.raises(NotImplementedError, match="comm_hierarchy.*grad_accum"):
        _overlap_trainer(
            mesh_of(dp=8), dcn_dp=2, comm_hierarchy="hierarchical",
            grad_accum=2,
        )


@pytest.mark.parametrize(
    "trainer_kw",
    [
        dict(comm_hierarchy="hierarchical"),
        dict(comm_hierarchy="auto"),
        dict(comm_hierarchy="flat"),
        dict(comm_hierarchy="auto", grad_bucket_mb=0.5),
        dict(comm_hierarchy="auto", update_sharding="sharded"),
        dict(comm_hierarchy="auto", grad_comm="int8"),
        dict(comm_hierarchy="auto", zero1=True),
    ],
    ids=["forced", "auto", "flat-on-hybrid", "bucketed", "sharded", "int8",
         "zero1"],
)
def test_hierarchy_legal_pairs_build(trainer_kw):
    _overlap_trainer(mesh_of(dp=8), dcn_dp=2, **trainer_kw)


def test_hierarchy_composes_with_precision_and_health():
    from distributeddeeplearning_tpu.config import HealthConfig

    _precision_trainer(
        _bf16_model(), mesh_of(dp=8), dcn_dp=2, comm_hierarchy="auto"
    )
    _overlap_trainer(
        mesh_of(dp=8), dcn_dp=2, comm_hierarchy="auto",
        health=HealthConfig(enabled=True),
    )


def test_cli_threads_hierarchy_knobs():
    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, OptimConfig, TrainConfig,
    )

    cfg = Config(
        model=ModelConfig(
            name="gpt2",
            kwargs=dict(size="tiny", vocab_size=128, max_len=32,
                        dropout_rate=0.0),
        ),
        data=DataConfig(kind="synthetic_tokens", batch_size=8, seq_len=16,
                        vocab_size=128),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(steps=1, task="lm", comm_hierarchy="auto"),
        mesh=MeshConfig(dp=8, dcn_dp=2),
    )
    _, _, trainer, _ = build_all(cfg)
    assert trainer.comm_hierarchy == "auto"
    assert trainer.dcn_dp == 2
    assert trainer._hier_topo is not None
    assert trainer._hier_topo.ici == 4


def test_cli_fences_hierarchy_before_mesh_build():
    # The mode-name fence must fire in build_all even when the mesh itself
    # would be buildable — by name, before any device work.
    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, OptimConfig, TrainConfig,
    )

    cfg = Config(
        model=ModelConfig(
            name="gpt2",
            kwargs=dict(size="tiny", vocab_size=128, max_len=32),
        ),
        data=DataConfig(kind="synthetic_tokens", batch_size=8, seq_len=16,
                        vocab_size=128),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(steps=1, task="lm", comm_hierarchy="hierarchical"),
        mesh=MeshConfig(dp=8, dcn_dp=1),
    )
    with pytest.raises(ValueError, match="comm_hierarchy"):
        build_all(cfg)


# ---------------------------------------------------------------------------
# Serving speculation fence matrix (serving.speculation x kernel/K/sampling)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("speculation,kernel,block_size,err,match", [
    # the L>1 kernel gap: the Pallas paged kernel is single-token, the
    # verify forward is K+1 wide — fenced until the multi-token kernel
    ("ngram:2", "pallas", 8, NotImplementedError, "pallas"),
    # K bounds: the page table is widened by exactly one draft window
    ("ngram:4", "reference", 4, NotImplementedError, "block_size"),
    ("ngram:16", "reference", 16, NotImplementedError, "block_size"),
    ("ngram:0", "reference", 16, ValueError, "K must be >= 1"),
    ("ngram:-3", "reference", 16, ValueError, "K must be >= 1"),
    # format errors, by name
    ("ngram:", "reference", 16, ValueError, "speculation"),
    ("ngram:two", "reference", 16, ValueError, "speculation"),
    ("lookahead:2", "reference", 16, ValueError, "speculation"),
])
def test_speculation_fence_matrix(speculation, kernel, block_size, err, match):
    from distributeddeeplearning_tpu.config import Config, ModelConfig, ServingConfig
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(
            speculation=speculation, attn_kernel=kernel,
            block_size=block_size,
        ),
    )
    with pytest.raises(err, match=match):
        check_serving_composition(cfg)


@pytest.mark.parametrize("speculation,kernel,block_size", [
    ("off", "reference", 16),
    ("off", "pallas", 16),        # pallas alone is fine
    ("ngram:3", "reference", 4),  # K < block_size
    ("ngram:15", "reference", 16),
    ("ngram:1", "reference", 2),  # smallest legal window
])
def test_speculation_legal_pairs_pass(speculation, kernel, block_size):
    from distributeddeeplearning_tpu.config import Config, ModelConfig, ServingConfig
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(
            speculation=speculation, attn_kernel=kernel,
            block_size=block_size,
        ),
    )
    check_serving_composition(cfg)  # must not raise


# ---------------------------------------------------------------------------
# Replica router fence matrix (serving.replicas x policies x batching)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,err,match", [
    # replica count bounds: 0 and negatives name the knob
    (dict(replicas=0), ValueError, "serving.replicas must be >= 1"),
    (dict(replicas=-2), ValueError, "serving.replicas must be >= 1"),
    # policy typos fail by name even at replicas=1 (no silent ignore)
    (dict(router_policy="fastest"), ValueError, "router_policy"),
    (dict(replicas=2, router_policy="round-robin"), ValueError,
     "router_policy"),
    (dict(shed_policy="lifo"), ValueError, "shed_policy"),
    (dict(shed_policy="deadline", shed_percentile=0.0), ValueError,
     "shed_percentile"),
    (dict(shed_policy="deadline", shed_percentile=101.0), ValueError,
     "shed_percentile"),
])
def test_router_fence_matrix(kwargs, err, match):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(**kwargs))
    with pytest.raises(err, match=match):
        check_serving_composition(cfg)


@pytest.mark.parametrize("kwargs", [
    dict(replicas=1),
    dict(replicas=4, router_policy="round_robin"),
    dict(replicas=2, shed_policy="deadline", shed_percentile=99.0),
    # router x speculation COMPOSES: each replica drafts/verifies its own
    # lanes; the compile pin just widens to replicas * (buckets + 2) —
    # pinned live in tests/test_serving_router.py.
    dict(replicas=2, speculation="ngram:3"),
])
def test_router_legal_compositions_pass(kwargs):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(**kwargs))
    check_serving_composition(cfg)  # must not raise


# ---------------------------------------------------------------------------
# Prefix-cache fence matrix (serving.prefix_cache x buckets/batching/policy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,err,match", [
    # suffix buckets are meaningless without the cache: fail, don't ignore
    (dict(suffix_buckets=(4,)), ValueError,
     "suffix_buckets.*prefix_cache=False"),
    # malformed suffix bucket ladders fail by name
    (dict(prefix_cache=True, suffix_buckets=(4, 4)), ValueError,
     "strictly increasing"),
    (dict(prefix_cache=True, suffix_buckets=(8, 4)), ValueError,
     "strictly increasing"),
    (dict(prefix_cache=True, suffix_buckets=(0,)), ValueError,
     "strictly increasing"),
    # widths already compiled as prompt buckets: the compile pin would lie
    (dict(prefix_cache=True, suffix_buckets=(8,)), ValueError,
     "duplicate prompt_buckets"),
    # a suffix width at/above the largest prompt bucket is dead weight
    (dict(prefix_cache=True, suffix_buckets=(32,)), ValueError,
     "largest prompt bucket"),
    # affinity routing reads the trie digest: cache off means no digest
    (dict(router_policy="prefix_affinity"), ValueError,
     "prefix_affinity.*prefix_cache=False"),
    (dict(replicas=2, router_policy="prefix_affinity"), ValueError,
     "prefix_affinity.*prefix_cache=False"),
    # spill tier hangs off the trie: no trie, nothing to spill — fail
    # loudly instead of silently ignoring the budget
    (dict(spill_blocks=4), ValueError,
     "spill_blocks.*prefix_cache=False"),
    (dict(prefix_cache=True, spill_blocks=-1), ValueError,
     "spill_blocks must be >= 0"),
    (dict(prefix_cache=True, spill_blocks=4, spill_codec="nvfp4"),
     ValueError, "spill_codec"),
    # a codec with no spill budget is a silently-ignored knob: config bug
    (dict(prefix_cache=True, spill_codec="int8"), ValueError,
     "spill_blocks=0"),
])
def test_prefix_cache_fence_matrix(kwargs, err, match):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(prompt_buckets=(8, 16), **kwargs))
    with pytest.raises(err, match=match):
        check_serving_composition(cfg)


@pytest.mark.parametrize("kwargs", [
    dict(prefix_cache=True),
    dict(prefix_cache=True, suffix_buckets=(4,)),
    # prefix_affinity at replicas=1 is LEGAL: no router is built and a
    # single replica trivially owns every prefix — the policy knob ports
    # unchanged between fleet sizes.
    dict(replicas=1, prefix_cache=True, router_policy="prefix_affinity"),
    dict(replicas=3, prefix_cache=True, suffix_buckets=(4,),
         router_policy="prefix_affinity"),
    # prefix_cache x speculation composes (warm suffixes feed the same
    # verify loop); parity is pinned live in tests/test_serving_prefix.py.
    dict(prefix_cache=True, suffix_buckets=(4,), speculation="ngram:3"),
    # prefix_cache x sampled requests sharing a prefix is legal — the trie
    # stores KV, not sampled tokens, and the per-request rng chain is
    # fold_in(seed, request_id) on every admission path (cold, warm,
    # decode-route). This row pins the ABSENCE of a fence; the live
    # parity proof is test_serving_prefix.py::
    # test_sampled_requests_sharing_a_prefix_are_legal.
    dict(prefix_cache=True, suffix_buckets=(4,)),
    # the spill tier composes with everything the trie composes with;
    # fp parity and the int8 bar are pinned live in
    # tests/test_serving_spill.py.
    dict(prefix_cache=True, spill_blocks=4),
    dict(prefix_cache=True, suffix_buckets=(4,), spill_blocks=4,
         spill_codec="int8"),
    dict(prefix_cache=True, suffix_buckets=(4,), spill_blocks=4,
         speculation="ngram:3"),
    dict(replicas=3, prefix_cache=True, spill_blocks=4,
         router_policy="prefix_affinity"),
])
def test_prefix_cache_legal_compositions_pass(kwargs):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(prompt_buckets=(8, 16), **kwargs))
    check_serving_composition(cfg)  # must not raise


# ---------------------------------------------------------------------------
# Quantized-KV fence matrix (serving.kv_quant x codec)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,err,match", [
    # unknown mode fails by name, not by downstream shape error
    (dict(kv_quant="int4"), ValueError, "kv_quant"),
    (dict(kv_quant="fp8"), ValueError, "kv_quant"),
    # double quantization: int8 pool blocks spilled through the int8
    # spill codec would re-quantize already-quantized bytes — fenced as
    # a config bug (keep spill_codec='fp', the bitwise pass-through)
    (dict(kv_quant="int8", prefix_cache=True, spill_blocks=4,
          spill_codec="int8"), ValueError, "kv_quant.*spill_codec"),
])
def test_kv_quant_fence_matrix(kwargs, err, match):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(prompt_buckets=(8, 16), **kwargs))
    with pytest.raises(err, match=match):
        check_serving_composition(cfg)


@pytest.mark.parametrize("kwargs", [
    dict(kv_quant="int8"),
    # int8 pool x prefix cache: published blocks are immutable int8 +
    # scale rows, content-addressing keys token ids, not bytes — parity
    # pinned live in tests/test_serving.py.
    dict(kv_quant="int8", prefix_cache=True, suffix_buckets=(4,)),
    # int8 pool x fp spill: the spill path device_gets whatever the pool
    # leaves hold — already-int8 payloads ride through bitwise.
    dict(kv_quant="int8", prefix_cache=True, spill_blocks=4),
    # int8 pool x speculation: verify reads the same dequantized pool.
    dict(kv_quant="int8", speculation="ngram:3"),
    # both kernels read the same quantized layout (parity pinned in
    # tests/test_paged_attention.py).
    dict(kv_quant="int8", attn_kernel="pallas"),
    dict(kv_quant="int8", attn_kernel="reference"),
])
def test_kv_quant_legal_compositions_pass(kwargs):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(model=ModelConfig(name="gpt2"),
                 serving=ServingConfig(prompt_buckets=(8, 16), **kwargs))
    check_serving_composition(cfg)  # must not raise


# ---------------------------------------------------------------------------
# Socket fleet fence matrix (cli serve --fleet x ports/heartbeats)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fleet,kwargs,err,match", [
    # fleet size bounds name the flag
    (0, {}, ValueError, "fleet must be >= 1"),
    (-3, {}, ValueError, "fleet must be >= 1"),
    # endpoint config: bad host/port fail before any process spawns
    (2, dict(worker_host=""), ValueError, "worker_host"),
    (2, dict(worker_host="   "), ValueError, "worker_host"),
    (2, dict(worker_port=-1), ValueError, "worker_port"),
    (2, dict(worker_port=70000), ValueError, "worker_port"),
    # worker i binds worker_port + i: the last worker must not overflow
    (4, dict(worker_port=65534), ValueError, "worker_port"),
    # heartbeat cadence: the router's policies run on pushed state — a
    # worker that never heartbeats is permanently stale
    (2, dict(heartbeat_interval_s=0.0), ValueError,
     "heartbeat_interval_s"),
    (2, dict(heartbeat_interval_s=-1.0), ValueError,
     "heartbeat_interval_s"),
    # a timeout under one interval quarantines healthy workers
    (2, dict(heartbeat_interval_s=0.5, heartbeat_timeout_s=0.25),
     ValueError, "heartbeat_timeout_s"),
])
def test_fleet_fence_matrix(fleet, kwargs, err, match):
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import check_fleet_composition

    cfg = ServingConfig(**kwargs)
    with pytest.raises(err, match=match):
        check_fleet_composition(cfg, fleet)


@pytest.mark.parametrize("fleet,kwargs", [
    (1, {}),
    (4, dict(worker_port=65532)),  # 65532..65535: exactly fits
    (2, dict(heartbeat_timeout_s=0.0)),  # 0 = staleness sweep disabled
    # the capability compositions the fleet must keep serving: affinity
    # needs the trie, quant and speculation are per-engine features the
    # transport never sees (parity pinned in tests/test_serving_worker.py)
    (4, dict(prefix_cache=True, router_policy="prefix_affinity")),
    (2, dict(kv_quant="int8")),
    (2, dict(speculation="ngram:3")),
    (4, dict(prefix_cache=True, router_policy="prefix_affinity",
             kv_quant="int8", speculation="ngram:3")),
])
def test_fleet_legal_compositions_pass(fleet, kwargs):
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import check_fleet_composition

    check_fleet_composition(ServingConfig(**kwargs), fleet)  # must not raise


# ---------------------------------------------------------------------------
# Self-healing fleet fence matrix (restart budget x backoff x fault DSL)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,fleet,err,match", [
    # restart budget: negatives name the knob (0 is legal = never restart)
    (dict(max_worker_restarts=-1), 2, ValueError,
     "max_worker_restarts"),
    # backoff shape: base must be positive and <= cap
    (dict(restart_backoff_base_s=0.0), 2, ValueError,
     "restart_backoff"),
    (dict(restart_backoff_base_s=2.0, restart_backoff_max_s=1.0), 2,
     ValueError, "restart_backoff"),
    # checkpoint cadence: negative, and cadence without a spill tier
    (dict(spill_checkpoint_every_s=-0.5), 2, ValueError,
     "spill_checkpoint_every_s"),
    (dict(spill_checkpoint_every_s=1.0, spill_blocks=0), 2, ValueError,
     "spill_checkpoint_every_s"),
    # fault DSL: unknown kinds and malformed steps die at config time
    (dict(fault_injection="oom:3"), 2, ValueError, "fault_injection"),
    (dict(fault_injection="worker_crash"), 2, ValueError,
     "expected '<kind>:K'"),
    (dict(fault_injection="worker_crash:-1"), 2, ValueError,
     "expected '<kind>:K'"),
    (dict(fault_injection="worker_hang:two"), 2, ValueError,
     "expected '<kind>:K'"),
    # fault injection x in-process serve: no worker process to kill
    (dict(fault_injection="worker_crash:5"), 0, NotImplementedError,
     "in-process"),
])
def test_fleet_healing_fence_matrix(kwargs, fleet, err, match):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(**kwargs),
    )
    with pytest.raises(err, match=match):
        check_serving_composition(cfg, fleet=fleet)


@pytest.mark.parametrize("kwargs,fleet", [
    # the chaos harness composition: fault x fleet x prefix cache + spill
    (dict(fault_injection="worker_crash:18", prefix_cache=True,
          suffix_buckets=(8,), prompt_buckets=(16, 32, 64),
          spill_blocks=24, spill_checkpoint_every_s=0.05,
          max_worker_restarts=2), 2),
    # every fault kind is spec-able
    (dict(fault_injection="worker_hang:3"), 2),
    (dict(fault_injection="conn_drop:0"), 2),
    (dict(fault_injection="heartbeat_stall:7"), 3),
    # healing knobs alone, in-process: legal (they are simply inert)
    (dict(max_worker_restarts=5, restart_backoff_base_s=0.1,
          restart_backoff_max_s=10.0), 0),
    # budget 0 (quarantine forever) is a legal degraded mode
    (dict(max_worker_restarts=0), 2),
    # fault x kv_quant x spill tier: the full hierarchy under chaos
    (dict(fault_injection="worker_crash:9", prefix_cache=True,
          suffix_buckets=(8,), prompt_buckets=(16, 32, 64),
          spill_blocks=16, kv_quant="int8", spill_checkpoint_every_s=0.1),
     2),
])
def test_fleet_healing_legal_pairs_pass(kwargs, fleet):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(**kwargs),
    )
    check_serving_composition(cfg, fleet=fleet)  # must not raise


# ---------------------------------------------------------------------------
# Disaggregation fence matrix (serving.role x prefill_replicas x fleet)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,fleet,err,match", [
    # role domain: typos name the knob and the legal set
    (dict(role="draft"), 0, ValueError, "serving.role"),
    (dict(role="Prefill"), 0, ValueError, "serving.role"),
    # any non-unified role needs the trie — it IS the handoff ledger
    (dict(role="prefill"), 0, ValueError,
     "role='prefill' x prefix_cache=False"),
    (dict(role="decode"), 0, ValueError,
     "role='decode' x prefix_cache=False"),
    # prefill never decodes, so decode-side speculation on a prefill
    # replica is dead config: fail, don't silently ignore
    (dict(role="prefill", prefix_cache=True, speculation="ngram:3"), 0,
     ValueError, "speculation"),
    # split topology knobs: negative count; split without a fleet; split
    # that leaves no decode replica; split without the trie
    (dict(prefill_replicas=-1), 0, ValueError, "prefill_replicas"),
    (dict(prefill_replicas=1, prefix_cache=True), 0, ValueError,
     "in-process"),
    (dict(prefill_replicas=4, prefix_cache=True), 4, ValueError,
     "at least one decode replica"),
    (dict(prefill_replicas=5, prefix_cache=True), 4, ValueError,
     "at least one decode replica"),
    (dict(prefill_replicas=1), 4, ValueError, "prefix_cache=true"),
    # handoff chunking floor names the knob
    (dict(handoff_blocks_per_frame=0), 0, ValueError,
     "handoff_blocks_per_frame"),
])
def test_disagg_fence_matrix(kwargs, fleet, err, match):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(**kwargs),
    )
    with pytest.raises(err, match=match):
        check_serving_composition(cfg, fleet=fleet)


@pytest.mark.parametrize("kwargs,fleet", [
    # single-role engines are legal alone (tests build them directly);
    # only the ROUTER can see a whole-fleet topology hole
    (dict(role="prefill", prefix_cache=True), 0),
    (dict(role="decode", prefix_cache=True), 0),
    # decode replicas may keep speculation — drafting is decode-side work
    (dict(role="decode", prefix_cache=True, speculation="ngram:3"), 0),
    # the bench topology: 1 prefill + 3 decode over affinity routing
    (dict(prefill_replicas=1, prefix_cache=True, suffix_buckets=(8,),
          router_policy="prefix_affinity"), 4),
    # split x the full serving stack: quant pool + host spill tier
    (dict(prefill_replicas=2, prefix_cache=True, kv_quant="int8",
          spill_blocks=16), 4),
    # tighter chunking is a tuning knob, not a fence
    (dict(prefill_replicas=1, prefix_cache=True,
          handoff_blocks_per_frame=1), 2),
])
def test_disagg_legal_compositions_pass(kwargs, fleet):
    from distributeddeeplearning_tpu.config import (
        Config, ModelConfig, ServingConfig,
    )
    from distributeddeeplearning_tpu.serving import check_serving_composition

    cfg = Config(
        model=ModelConfig(name="gpt2"),
        serving=ServingConfig(**kwargs),
    )
    check_serving_composition(cfg, fleet=fleet)  # must not raise


@pytest.mark.parametrize("roles,match", [
    (["decode", "decode"], "decode-only fleet"),
    (["prefill", "prefill"], "prefill-only fleet"),
])
def test_router_rejects_single_phase_fleet_topology(roles, match):
    # Each engine's role is a legal config alone; only the router sees
    # every member, so the whole-fleet topology hole is fenced at fleet
    # build — by name, before any request is admitted.
    import dataclasses
    import socket

    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import ReplicaRouter, SocketReplica

    cfg = ServingConfig(slots=2, block_size=4, hbm_budget_mb=8,
                        max_seq_len=32, prompt_buckets=(8,),
                        prefix_cache=True, suffix_buckets=(4,))
    socks = []
    transports = []
    try:
        for i, role in enumerate(roles):
            a, b = socket.socketpair()
            socks += [a, b]
            hello = {"type": "hello", "replica": i, "role": role,
                     "block_size": 4, "slots": 2, "gauges": {}}
            transports.append(
                SocketReplica(i, a, hello, clock=lambda: 0.0)
            )
        with pytest.raises(ValueError, match=match):
            ReplicaRouter(None, None, dataclasses.replace(cfg),
                          clock=lambda: 0.0, transports=transports)
    finally:
        for s in socks:
            s.close()
