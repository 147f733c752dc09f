"""M0: collective wrapper numerics on the 8-device CPU-sim mesh.

Each collective is checked against a numpy-computed expectation — this is the
parity harness the NCCL layer of the reference would be tested with, minus the
transport (XLA emits the collectives inside one compiled program).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu import comms


def shmap(f, mesh, in_specs, out_specs):
    # check_vma=False: collectives like all_gather produce value-replicated
    # outputs that the varying-manual-axes checker can't statically prove.
    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


def test_psum(mesh8):
    x = jnp.arange(8.0)
    out = shmap(lambda v: comms.psum(v, "dp"), mesh8, P("dp"), P())(x)
    assert out.shape == (1,)
    np.testing.assert_allclose(out, [28.0])


def test_pmean(mesh8):
    x = jnp.arange(8.0)
    out = shmap(lambda v: comms.pmean(v, "dp"), mesh8, P("dp"), P())(x)
    np.testing.assert_allclose(out, [3.5])


def test_all_gather_tiled(mesh8):
    x = jnp.arange(16.0).reshape(8, 2)
    f = shmap(
        lambda v: comms.all_gather(v, "dp"), mesh8, P("dp", None), P(None, None)
    )
    out = f(x)
    # Every shard holds the full array; output is the full array.
    np.testing.assert_allclose(out, x)


def test_reduce_scatter(mesh8):
    # Each member holds the full vector [0..7]; reduce-scatter sums over the 8
    # members and leaves member i with element i*8... wait: psum_scatter over a
    # replicated input of shape [8] gives member i -> 8 * x[i].
    x = jnp.tile(jnp.arange(8.0), (8, 1))  # [dp=8, 8]
    f = shmap(
        lambda v: comms.reduce_scatter(v[0], "dp"), mesh8, P("dp", None), P("dp")
    )
    out = f(x)
    np.testing.assert_allclose(out, 8.0 * jnp.arange(8.0))


def test_ring_shift(mesh8):
    x = jnp.arange(8.0)
    f = shmap(lambda v: comms.ring_shift(v, "dp", shift=1), mesh8, P("dp"), P("dp"))
    out = f(x)
    # member i receives from i-1: [7, 0, 1, ..., 6]
    np.testing.assert_allclose(out, jnp.roll(x, 1))


def test_ring_shift_negative(mesh8):
    x = jnp.arange(8.0)
    f = shmap(lambda v: comms.ring_shift(v, "dp", shift=-1), mesh8, P("dp"), P("dp"))
    np.testing.assert_allclose(f(x), jnp.roll(x, -1))


def test_broadcast_from_src(mesh8):
    x = jnp.arange(8.0)
    f = shmap(lambda v: comms.broadcast(v, "dp", src=3), mesh8, P("dp"), P("dp"))
    np.testing.assert_allclose(f(x), jnp.full((8,), 3.0))


def test_broadcast_pytree(mesh8):
    tree = {"a": jnp.arange(8.0), "b": jnp.arange(8.0) * 10}
    f = shmap(
        lambda v: comms.broadcast(v, "dp", src=0),
        mesh8,
        ({"a": P("dp"), "b": P("dp")},),
        {"a": P("dp"), "b": P("dp")},
    )
    out = f(tree)
    np.testing.assert_allclose(out["a"], jnp.zeros(8))
    np.testing.assert_allclose(out["b"], jnp.zeros(8))


def test_all_to_all(mesh8):
    # [seq-shard, heads] -> [seq, heads-shard]: the Ulysses reshard.
    seq, heads = 16, 8
    x = jnp.arange(seq * heads, dtype=jnp.float32).reshape(seq, heads)
    f = shmap(
        lambda v: comms.all_to_all(v, "dp", split_axis=1, concat_axis=0),
        mesh8,
        P("dp", None),
        P(None, "dp"),
    )
    out = f(x)
    np.testing.assert_allclose(out, x)


def test_axis_primitives(mesh8):
    f = shmap(
        lambda: (
            comms.axis_index("dp")[None],
            jnp.full((1,), comms.axis_size("dp"), jnp.int32),
        ),
        mesh8,
        (),
        (P("dp"), P()),
    )
    idx, size = f()
    np.testing.assert_array_equal(idx, np.arange(8))
    assert int(size[0]) == 8


def test_megatron_fg_transposes_under_manual_ad(mesh8):
    # The f/g pair's raison d'être (parallel/pp.interleaved_1f1b): inside
    # shard_map(check_vma=False), a RAW lax.psum's transpose is psum — a
    # jax.vjp'd region crossing it multiplies the cotangent by the axis
    # size. g (psum_identity_bwd) pins the identity transpose; f
    # (identity_fwd_psum_bwd) pins the conjugate (sum of per-rank
    # contributions). Asserted against in-body vjp cotangents on an 8-way
    # axis.
    import jax

    def cotangent_of(fn):
        def body(w):
            _, vjp = jax.vjp(fn, w)
            (dw,) = vjp(jnp.ones(()))
            return dw[None]

        out = jax.shard_map(
            body, mesh=mesh8, in_specs=(P(),), out_specs=P("dp"),
            check_vma=False,
        )(jnp.ones(()))
        return np.asarray(out)

    # raw psum: transpose is psum -> cotangent is axis_size on every rank.
    raw = cotangent_of(lambda w: jax.lax.psum(w * 1.0, "dp"))
    np.testing.assert_array_equal(raw, np.full(8, 8.0))
    # g: identity transpose -> the full output cotangent, once, per rank.
    g = cotangent_of(lambda w: comms.psum_identity_bwd(w * 1.0, "dp"))
    np.testing.assert_array_equal(g, np.ones(8))
    # f: identity forward; transpose sums the per-rank contributions.
    f = cotangent_of(lambda w: comms.identity_fwd_psum_bwd(w * 1.0, "dp"))
    np.testing.assert_array_equal(f, np.full(8, 8.0))


def test_psum_identity_bwd_types_under_vma_on(mesh8):
    # The bwd rule must RE-VARY its cotangent over the reduced axis: with
    # jax_disable_bwd_checks=False (the stock config, pinned here) a bwd
    # rule returning an invariant cotangent for a varying primal is a
    # trace-time error under vma-ON shard_map.
    import jax

    old = jax.config.jax_disable_bwd_checks
    jax.config.update("jax_disable_bwd_checks", False)
    try:
        def body(w):
            # g's contract spans BOTH vma modes (the blocks use it
            # unconditionally): under vma-on its bwd must pcast the
            # cotangent back to varying — without that, stock JAX raises
            # "Custom VJP bwd rule must produce an output with the same
            # type". w replicated; per-rank slice compute; g at the exit;
            # jax's own invariant-input boundary supplies the sum.
            scale = jax.lax.axis_index("dp").astype(jnp.float32) + 1.0

            def fwd(t):
                return comms.psum_identity_bwd(t * scale, "dp")

            y, vjp = jax.vjp(fwd, w)
            (dw,) = vjp(jnp.ones_like(y))
            return dw

        out = jax.shard_map(
            body, mesh=mesh8, in_specs=(P(),), out_specs=P(),
        )(jnp.ones((1,)))
        # d/dw sum_r (r+1) * w = 36, identically on every rank.
        np.testing.assert_array_equal(np.asarray(out), np.full(1, 36.0))
    finally:
        jax.config.update("jax_disable_bwd_checks", old)
