"""Unit tests for ``comms_quant`` — block quantization, the compressed ring
collectives, and error feedback (PR: compressed gradient sync; design in
docs/GRADIENT_COMPRESSION.md).

The ring tests run the real ``shard_map`` + ``lax.ppermute`` path over the
8-device CPU sim and compare against the uncompressed numpy reduction; the
quantization-error bounds they assert are the block-quant noise floor, not
tolerances loosened until green (int8: ~0.2%% rms of the block amax per
requantization, accumulated over n-1 hops)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import helpers

from distributeddeeplearning_tpu import comms_quant as cq

N = 8  # conftest pins an 8-device CPU sim


def _ring(fn, x, mesh):
    """Run ``fn(flat_shard)`` inside shard_map over dp=8; input/output carry
    a leading member dim so every member's result comes back stacked."""
    shard = jax.shard_map(
        lambda s: fn(s[0])[None], mesh=mesh, in_specs=(P("dp"),),
        out_specs=P("dp"), check_vma=False,
    )
    return shard(x)


# ---------------------------------------------------------------------------
# Block quantization units
# ---------------------------------------------------------------------------


def test_block_scale_is_amax_over_127_and_extremes_hit_127():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(512,)).astype(np.float32))
    q, scale = cq.block_quantize(x, block_size=256)
    blocks = np.asarray(x).reshape(-1, 256)
    np.testing.assert_allclose(
        np.asarray(scale)[:, 0], np.abs(blocks).max(1) / 127.0, rtol=1e-6
    )
    # The max-abs element of every block maps to exactly +-127.
    assert np.all(np.abs(np.asarray(q)).reshape(-1, 256).max(1) == 127)


def test_grid_values_round_trip_exactly():
    # Values already on the quantization grid (q * scale) survive a
    # quantize->dequantize round trip bit-exactly — the property that makes
    # the ring's re-quantization of an EF-compressed tensor lossless.
    rng = np.random.default_rng(1)
    scale = np.float32(0.03125)  # power of two: q*scale exact in f32
    qs = rng.integers(-127, 128, size=(512,)).astype(np.float32)
    qs.reshape(-1, 256)[:, 0] = 127  # pin each block's amax to 127*scale
    x = jnp.asarray(qs * scale)
    out = cq.block_dequantize(*cq.block_quantize(x, 256))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_zero_block_quantizes_to_zero_without_nan():
    x = jnp.zeros((256,), jnp.float32)
    q, scale = cq.block_quantize(x, 256)
    assert float(scale[0, 0]) == 0.0
    out = cq.block_dequantize(q, scale)
    assert np.all(np.asarray(out) == 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


def test_quantization_error_bounded_by_half_step():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2048,)).astype(np.float32))
    out = cq.block_dequantize(*cq.block_quantize(x, 256))
    err = np.abs(np.asarray(out) - np.asarray(x)).reshape(-1, 256)
    step = np.abs(np.asarray(x)).reshape(-1, 256).max(1, keepdims=True) / 127.0
    assert np.all(err <= step / 2 + 1e-7)


def test_compression_ratio_values():
    assert cq.compression_ratio("fp32") == 1.0
    assert cq.compression_ratio("bf16") == 0.5
    assert cq.compression_ratio("int8", 256) == pytest.approx(
        (1 + 4 / 256) / 4
    )
    # Smaller blocks pay more scale overhead.
    assert cq.compression_ratio("int8", 32) > cq.compression_ratio("int8", 256)


def test_pad_to():
    assert cq._pad_to(jnp.ones((5,)), 4).shape == (8,)
    assert cq._pad_to(jnp.ones((8,)), 4).shape == (8,)
    padded = cq._pad_to(jnp.ones((5,)), 4)
    assert np.all(np.asarray(padded)[5:] == 0.0)


def test_unknown_mode_raises():
    with pytest.raises(ValueError, match="grad_comm"):
        cq.quantized_tree_all_reduce({"w": jnp.ones((4,))}, "dp", mode="fp8")


# ---------------------------------------------------------------------------
# Ring collectives (8-device CPU sim)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,tol", [("int8", 0.02), ("bf16", 0.01)])
def test_ring_all_reduce_matches_sum_and_is_member_identical(mode, tol):
    mesh = helpers.mesh_of(dp=N)
    rng = np.random.default_rng(3)
    m = N * 256  # one block per member chunk
    x = jnp.asarray(rng.normal(size=(N, m)).astype(np.float32))
    got = _ring(
        lambda s: cq.quantized_all_reduce_flat(s, "dp", mode=mode),
        x, mesh,
    )
    got = np.asarray(got)
    want = np.asarray(x).sum(0)
    # Bit-identical across members: the gather phase hands every member the
    # same DEcompressed chunk values, including the chunk's own reducer.
    assert np.all(got == got[0:1]), np.abs(got - got[0:1]).max()
    rel = np.linalg.norm(got[0] - want) / np.linalg.norm(want)
    assert rel < tol, rel


def test_ring_all_reduce_fp32_mode_is_exact_psum():
    mesh = helpers.mesh_of(dp=N)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(N, N * 256)).astype(np.float32))
    got = _ring(
        lambda s: cq.quantized_all_reduce_flat(s, "dp", mode="fp32"),
        x, mesh,
    )
    want = _ring(lambda s: jax.lax.psum(s, "dp"), x, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_ring_reduce_scatter_matches_all_reduce_chunks(mode):
    # psum_scatter semantics: member i's output is chunk i of (a run of) the
    # same compressed reduction — the extra hop re-quantizes the final
    # chunk, which is lossless (the payload is already on its grid).
    mesh = helpers.mesh_of(dp=N)
    rng = np.random.default_rng(5)
    m = N * 256
    x = jnp.asarray(rng.normal(size=(N, m)).astype(np.float32))
    rs = np.asarray(_ring(
        lambda s: cq.quantized_reduce_scatter_flat(s, "dp", mode=mode),
        x, mesh,
    ))
    ar = np.asarray(_ring(
        lambda s: cq.quantized_all_reduce_flat(s, "dp", mode=mode),
        x, mesh,
    ))
    chunks = ar[0].reshape(N, -1)
    np.testing.assert_array_equal(rs, chunks)


def test_tree_all_reduce_pads_odd_sizes_and_matches_psum_closely():
    # Leaf sizes deliberately not multiples of block/n: exercises _pad_to.
    mesh = helpers.mesh_of(dp=N)
    rng = np.random.default_rng(6)
    tree = {
        "w": jnp.asarray(rng.normal(size=(N, 5, 7)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(N, 11)).astype(np.float32)),
    }

    def body(w, b):
        summed, _ = cq.quantized_tree_all_reduce(
            {"w": w[0], "b": b[0]}, "dp", mode="int8", block_size=256
        )
        return summed["w"][None], summed["b"][None]

    shard = jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")), check_vma=False,
    )
    got_w, got_b = shard(tree["w"], tree["b"])
    for got, want in [
        (np.asarray(got_w)[0], np.asarray(tree["w"]).sum(0)),
        (np.asarray(got_b)[0], np.asarray(tree["b"]).sum(0)),
    ]:
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------


def test_ef_identity_sent_plus_residual_is_input():
    rng = np.random.default_rng(7)
    grads = {"w": jnp.asarray(rng.normal(size=(13, 3)).astype(np.float32))}
    residual = cq.zeros_residual(grads)
    sent, new_res = cq.ef_compress(
        grads, residual, mode="int8", block_size=256
    )
    # new_residual is EXACTLY the compression error (computed as total -
    # sent in f32, so the identity is bitwise).
    np.testing.assert_array_equal(
        np.asarray(sent["w"]) + np.asarray(new_res["w"]),
        np.asarray(grads["w"]),
    )
    assert np.any(np.asarray(new_res["w"]) != 0.0)  # compression is lossy


def test_ef_recompression_of_sent_is_lossless():
    # The decompressed send already sits on its block grid, so compressing
    # it again is exact — this is what makes the residual capture the FULL
    # send-side error even though the ring re-quantizes the payload.
    rng = np.random.default_rng(8)
    grads = {"w": jnp.asarray(rng.normal(size=(300,)).astype(np.float32))}
    sent, _ = cq.ef_compress(
        grads, cq.zeros_residual(grads), mode="int8", block_size=256
    )
    sent2, res2 = cq.ef_compress(
        sent, cq.zeros_residual(sent), mode="int8", block_size=256
    )
    np.testing.assert_array_equal(np.asarray(sent2["w"]), np.asarray(sent["w"]))
    assert np.all(np.asarray(res2["w"]) == 0.0)


def test_ef_residual_carries_into_next_step():
    # Two EF steps on a CONSTANT gradient: step 2 compresses g + r1, and the
    # mean of the two sends is closer to g than a single lossy send — the
    # EF-SGD property (error accumulates to zero mean instead of biasing).
    rng = np.random.default_rng(9)
    g = {"w": jnp.asarray(rng.normal(size=(256,)).astype(np.float32) * 1e-2)}
    r = cq.zeros_residual(g)
    sent1, r = cq.ef_compress(g, r, mode="int8", block_size=256)
    sent2, r = cq.ef_compress(g, r, mode="int8", block_size=256)
    g_np = np.asarray(g["w"])
    avg = (np.asarray(sent1["w"]) + np.asarray(sent2["w"])) / 2
    err_one = np.linalg.norm(np.asarray(sent1["w"]) - g_np)
    err_avg = np.linalg.norm(avg - g_np)
    assert err_avg < err_one


def test_ef_none_residual_passthrough():
    g = {"w": jnp.ones((4,))}
    sent, res = cq.ef_compress(g, None, mode="int8", block_size=256)
    assert sent is g and res is None
    sent, res = cq.ef_compress(g, {"w": jnp.zeros((4,))}, mode="fp32",
                               block_size=256)
    assert sent is g
