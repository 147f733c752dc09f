"""M0: logical-axis rule algebra."""

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu import sharding as sh


def test_default_rules_cover_vocabulary():
    names = {k for k, _ in sh.DEFAULT_LOGICAL_RULES}
    assert {"batch", "seq", "embed", "heads", "kv", "mlp", "vocab",
            "expert", "stage"} <= names


def test_make_rules_override():
    rules = dict(sh.make_rules(embed=None, mlp=("tp",)))
    assert rules["embed"] is None
    assert rules["mlp"] == ("tp",)
    assert rules["heads"] == "tp"  # untouched


def test_batch_sharding_places_batch_dim(mesh8):
    s = sh.batch_sharding(mesh8)
    x = jax.device_put(jnp.zeros((16, 4)), s)
    # 8-way dp: each shard holds 2 rows.
    assert x.addressable_shards[0].data.shape == (2, 4)


def test_logical_to_mesh_sharding(mesh_factory):
    mesh = mesh_factory(dp=2, fsdp=2, tp=2)
    spec_tree = {
        "kernel": nn.Partitioned(
            jnp.zeros((4, 4)), names=("embed", "mlp")
        ).get_partition_spec(),
        "bias": P("mlp"),
    }
    out = sh.logical_to_mesh_sharding(spec_tree, mesh)
    assert isinstance(out["kernel"], NamedSharding)
    assert out["kernel"].spec == P("fsdp", "tp")
    assert out["bias"].spec == P("tp")


def test_replicated(mesh8):
    s = sh.replicated(mesh8)
    x = jax.device_put(jnp.ones((4,)), s)
    assert x.addressable_shards[0].data.shape == (4,)


def test_constrain_outside_mesh_is_noop():
    x = jnp.ones((4, 4))
    y = sh.constrain(x, "batch", "embed")
    np.testing.assert_allclose(x, y)


def test_constrain_inside_shard_map_is_noop(mesh_factory):
    # In a shard_map body the mesh axes are Manual: the body holds its shard,
    # and with_sharding_constraint refuses a spec naming a Manual axis (jax
    # 0.9.0 raises where older releases ignored it) — with or without the
    # Trainer's activation mesh around the call.
    mesh = mesh_factory(dp=8)
    x = jnp.arange(64.0).reshape(16, 4)
    body = jax.shard_map(
        lambda v: sh.constrain(v, "batch", "embed") * 2.0,
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
    )
    np.testing.assert_array_equal(jax.jit(body)(x), x * 2.0)
    with sh.activation_mesh(mesh):
        np.testing.assert_array_equal(jax.jit(body)(x), x * 2.0)


def test_constrain_applies_default_rules_under_mesh(mesh_factory):
    # Inside jit under a mesh, constrain() must actually shard via the
    # default rules table without any ambient nn.logical_axis_rules context.
    mesh = mesh_factory(dp=4, fsdp=2)
    with jax.sharding.set_mesh(mesh):
        y = jax.jit(lambda v: sh.constrain(v, "batch", "embed"))(
            jnp.ones((16, 4))
        )
    assert isinstance(y.sharding, NamedSharding)
    assert y.sharding.spec[0] in (("dp", "fsdp"), "dp")
    # batch dim actually split 8-ways across dp*fsdp
    assert y.addressable_shards[0].data.shape[0] == 2
