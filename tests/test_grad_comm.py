"""Integration tests for the compressed gradient sync (``grad_comm`` knob):
loss parity vs the fp32 partitioner path, the error-feedback residual in
TrainState, composition fences, and the HLO-level byte win the subsystem
exists for (docs/GRADIENT_COMPRESSION.md)."""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

import helpers

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.parallel.fsdp import grad_sync_bytes
from distributeddeeplearning_tpu.train import (
    Trainer, get_task, make_optimizer,
)
from distributeddeeplearning_tpu.utils.hlo import collective_bytes

N = 8


# ---------------------------------------------------------------------------
# Parity: the whole point — compressed sync must train like fp32
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,atol", [("int8", 5e-3), ("bf16", 5e-3)])
def test_lossy_sync_loss_parity_with_fp32(mode, atol):
    """int8/bf16 + error feedback vs the partitioner's fp32 all-reduce on
    identical seeds/data over dp=8: per-step losses must track within the
    block-quant noise floor (observed |delta| ~2e-4; the bound leaves
    headroom without admitting a broken ring, which diverges by step 2)."""
    fp32, _ = helpers.train_tiny_gpt2(helpers.mesh_of(dp=N), n_steps=6)
    lossy, _ = helpers.train_tiny_gpt2(
        helpers.mesh_of(dp=N), n_steps=6, grad_comm=mode
    )
    np.testing.assert_allclose(lossy, fp32, atol=atol)


def test_int8_convergence_leg():
    """Longer leg: 20 steps of int8+EF keep training (monotone-ish loss
    decrease) and end within a small gap of fp32 — quantization error with
    EF must not bias convergence, only jitter it."""
    fp32, _ = helpers.train_tiny_gpt2(helpers.mesh_of(dp=N), n_steps=20)
    int8, _ = helpers.train_tiny_gpt2(
        helpers.mesh_of(dp=N), n_steps=20, grad_comm="int8"
    )
    assert int8[-1] < int8[0]  # it actually trains
    assert abs(int8[-1] - fp32[-1]) < 0.02, (int8[-1], fp32[-1])
    # Cumulative drift over 20 steps stays small at every step.
    np.testing.assert_allclose(int8, fp32, atol=2e-2)


def test_zero1_composes_with_int8():
    # ZeRO-1 is optimizer-state placement downstream of the (replicated)
    # synced grads — same math, so same losses as plain-DP int8.
    plain, _ = helpers.train_tiny_gpt2(
        helpers.mesh_of(dp=N), n_steps=4, grad_comm="int8"
    )
    zero1, _ = helpers.train_tiny_gpt2(
        helpers.mesh_of(dp=N), n_steps=4, grad_comm="int8", zero1=True
    )
    np.testing.assert_allclose(zero1, plain, atol=1e-5)


def test_residual_state_threaded_and_sharded():
    mesh = helpers.mesh_of(dp=N)
    _, state = helpers.train_tiny_gpt2(mesh, n_steps=2, grad_comm="int8")
    leaves = jax.tree.leaves(state.grad_residual)
    assert leaves, "grad_residual missing from TrainState"
    for leaf in leaves:
        assert leaf.shape[0] == N  # one residual per dp member
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec[0] == "dp"
    # EF actually engaged: residuals are the (nonzero) compression error.
    assert any(np.any(np.asarray(leaf) != 0.0) for leaf in leaves)


def test_fp32_state_has_no_residual():
    _, state = helpers.train_tiny_gpt2(helpers.mesh_of(dp=N), n_steps=1)
    assert state.grad_residual is None
    # Absent from the pytree: fp32 checkpoints are unchanged by this PR.
    assert not any(
        "grad_residual" in str(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(state)[0]
    )


# ---------------------------------------------------------------------------
# Composition fences
# ---------------------------------------------------------------------------


def _tiny_model():
    return models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0
    )


def _trainer(mesh, model=None, **kw):
    return Trainer(
        model or _tiny_model(), make_optimizer("adamw", 1e-3),
        get_task("lm"), mesh, donate=False, **kw,
    )


def test_fence_unknown_mode():
    with pytest.raises(ValueError, match="grad_comm"):
        _trainer(helpers.mesh_of(dp=N), grad_comm="fp8")


@pytest.mark.parametrize("axes", [dict(dp=4, fsdp=2), dict(dp=4, tp=2)])
def test_fence_non_dp_mesh(axes):
    with pytest.raises(NotImplementedError, match="pure-DP"):
        _trainer(helpers.mesh_of(**axes), grad_comm="int8")


def test_fence_grad_accum():
    with pytest.raises(NotImplementedError, match="grad_accum"):
        _trainer(helpers.mesh_of(dp=N), grad_comm="int8", grad_accum=2)


def test_fence_pipelined_model():
    mesh = helpers.mesh_of(dp=2, pp=2)
    model = models.get_model(
        "gpt2_pp", size="tiny", vocab_size=64, max_len=32,
        num_stages=2, num_microbatches=2, mesh=mesh,
    )
    with pytest.raises(NotImplementedError, match="pipelined"):
        _trainer(mesh, model=model, grad_comm="int8")


def test_fp32_default_untouched_on_busy_mesh():
    # The fences must not fire for the default mode: fsdp/tp/pp users see
    # zero behavior change from this subsystem existing.
    _trainer(helpers.mesh_of(dp=4, fsdp=2))  # no raise


# ---------------------------------------------------------------------------
# HLO evidence: the bytes actually shrink
# ---------------------------------------------------------------------------


def _compiled_step_text(mesh, **trainer_kw):
    # Shared HLO-compile helper (helpers.compiled_step_text) so the
    # precision tests reuse the same parser instead of a per-file copy.
    ds = data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=64, seed=0
    )
    trainer = _trainer(mesh, model=_tiny_model(), **trainer_kw)
    return helpers.compiled_step_text(trainer, ds.batch(0), mesh)


_sync_wire_bytes = helpers.sync_wire_bytes


def test_int8_step_emits_compressed_permutes_and_cuts_sync_bytes():
    mesh = helpers.mesh_of(dp=N)
    fp32_text = _compiled_step_text(mesh)
    int8_text = _compiled_step_text(mesh, grad_comm="int8")
    # The quantized step's sync is explicit ring hops on int8 payloads.
    assert collective_bytes(int8_text, N)["collective-permute"], (
        "no collective-permutes in the quantized step"
    )
    assert "s8[" in int8_text, "no int8 payloads on the wire"
    # And the ring-model wire bytes land ~4x under fp32 (int8 + one f32
    # scale per 256 elements + padding => a bit under 4).
    ratio = _sync_wire_bytes(fp32_text, N) / _sync_wire_bytes(int8_text, N)
    assert 3.0 < ratio < 4.5, ratio


def test_grad_sync_bytes_analytic_ratio():
    # The bench-row accounting (parallel/fsdp.grad_sync_bytes) must agree
    # with the design ratio: (1 + 4/256)/4 bytes per f32 element.
    tree = {"w": np.zeros((1024, 1024)), "b": np.zeros((1024,))}
    fp32 = grad_sync_bytes(tree, mode="fp32", n_members=8)
    int8 = grad_sync_bytes(tree, mode="int8", n_members=8)
    bf16 = grad_sync_bytes(tree, mode="bf16", n_members=8)
    assert fp32 > bf16 > int8 > 0
    assert fp32 / int8 == pytest.approx(4 / (1 + 4 / 256), rel=1e-3)
    assert fp32 / bf16 == pytest.approx(2.0, rel=1e-6)
