"""Mixed-precision subsystem (``train.precision``, docs/MIXED_PRECISION.md):
fp32 masters + bf16 compute copy + (bf16_full) low-precision Adam moments.

Contracts pinned here:
- ``precision="fp32"`` is a Python-level no-op: the compiled step is
  TEXT-IDENTICAL to a pre-PR Trainer (golden identity — fp32 users see zero
  numerical or performance change from this subsystem existing);
- bf16 trains at parity with fp32 on the tiny-GPT-2 leg while masters and
  (plain-bf16) moments stay float32;
- the byte win exists in the partitioner-emitted HLO: dp grad all-reduce
  and ZeRO-1 param all-gather payloads halve vs fp32 (read at the
  post-SPMD-partitioning stage — the CPU backend's float normalization
  re-promotes bf16 collectives afterwards; a TPU keeps them, see
  helpers.compiled_step_text);
- stochastic rounding (ops.fused_adamw.stochastic_round) is exact on
  representable values, lands only on the two bf16 neighbors, is unbiased,
  deterministic per key, and passes non-finites through;
- checkpoints are policy-agnostic: masters are the durable schema, so a
  bf16-saved state restores bit-exactly under fp32 and vice versa, and the
  PR-4 corrupt-fallback walk still works under bf16;
- composition: fused K-step dispatch is bit-identical under bf16, int8
  grad_comm keeps its fp32 error-feedback residual, and ZeRO-1 + bf16_full
  cuts per-member durable state bytes >= 3x (the ISSUE acceptance bar).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import helpers

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.checkpoint import CheckpointManager
from distributeddeeplearning_tpu.ops.fused_adamw import stochastic_round
from distributeddeeplearning_tpu.precision import Policy, get_policy
from distributeddeeplearning_tpu.sharding import batch_sharding
from distributeddeeplearning_tpu.train import (
    Trainer, get_task, make_optimizer,
)

N = 8


def _tokens(vocab=256):
    return data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=vocab, seed=0, n_distinct=4
    )


def _trainer(mesh, *, precision="fp32", vocab=256, max_len=64, **kw):
    """gpt2-tiny trainer whose model dtype follows the policy's compute
    dtype — the same derivation cli.build_all performs from the config."""
    policy = get_policy(precision)
    model_kw = {}
    if policy.mixed:
        model_kw["dtype"] = policy.compute_dtype
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=vocab, max_len=max_len,
        dropout_rate=0.0, **model_kw,
    )
    tx = make_optimizer("adamw", 1e-3, precision=precision)
    return Trainer(
        model, tx, get_task("lm"), mesh, donate=False, precision=precision,
        **kw,
    )


def _hlo(mesh, *, precision="fp32", spmd=False, **trainer_kw):
    # vocab 64 / max_len 32: smallest model that still exercises every
    # layer, to keep the per-policy compiles cheap.
    tr = _trainer(
        mesh, precision=precision, vocab=64, max_len=32, **trainer_kw
    )
    return helpers.compiled_step_text(tr, _tokens(64).batch(0), mesh,
                                      spmd=spmd)


# ---------------------------------------------------------------------------
# Policy table
# ---------------------------------------------------------------------------


def test_policy_table():
    fp32 = get_policy("fp32")
    assert not fp32.mixed and fp32.compute_dtype == jnp.float32

    bf16 = get_policy("bf16")
    assert bf16.mixed
    assert bf16.param_dtype == jnp.float32          # masters
    assert bf16.compute_dtype == jnp.bfloat16       # fwd/bwd copy
    assert bf16.moment_dtype == jnp.float32         # Adam state untouched

    full = get_policy("bf16_full")
    assert full.moment_dtype == jnp.bfloat16 and full.stochastic_rounding

    # Policy objects pass through (the cli hands resolved policies around).
    assert get_policy(bf16) is bf16
    assert isinstance(bf16, Policy)


def test_policy_unknown_lists_choices():
    with pytest.raises(ValueError, match="fp32.*bf16.*bf16_full"):
        get_policy("fp16")


# ---------------------------------------------------------------------------
# Golden identity: fp32 is a no-op at the Python level
# ---------------------------------------------------------------------------


def test_fp32_policy_compiles_to_identical_program():
    """The cast helpers return their input object under fp32, so the traced
    program — and therefore the compiled text — must be IDENTICAL to a
    Trainer that predates this subsystem (no precision kwarg at all)."""
    mesh = helpers.mesh_of(dp=N)
    ds = _tokens(64)
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=64, max_len=32, dropout_rate=0.0
    )
    legacy = Trainer(  # exactly what a pre-PR caller constructs
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        donate=False,
    )
    legacy_text = helpers.compiled_step_text(legacy, ds.batch(0), mesh)
    fp32_text = _hlo(mesh, precision="fp32")
    assert legacy_text == fp32_text


# ---------------------------------------------------------------------------
# Training parity + state dtypes
# ---------------------------------------------------------------------------


def test_bf16_tracks_fp32_and_masters_stay_fp32():
    mesh = helpers.mesh_of(dp=N)
    fp32, _ = helpers.train_tiny_gpt2(mesh, n_steps=6)
    bf16, state = helpers.train_tiny_gpt2(
        mesh, n_steps=6, dtype=jnp.bfloat16, precision="bf16"
    )
    # bf16 rounding of activations/grads jitters the trajectory but must
    # not change it materially on this leg (observed |delta| ~1e-3).
    np.testing.assert_allclose(bf16, fp32, atol=5e-2)
    assert bf16[-1] < bf16[0]
    # Masters and plain-bf16 Adam moments are untouched fp32.
    for leaf in jax.tree.leaves(state.params):
        assert leaf.dtype == jnp.float32, leaf.dtype
    for leaf in jax.tree.leaves(state.opt_state):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype


def test_bf16_full_stores_moments_in_bf16_and_trains():
    mesh = helpers.mesh_of(dp=N)
    losses, state = helpers.train_tiny_gpt2(
        mesh, n_steps=6, dtype=jnp.bfloat16, precision="bf16_full"
    )
    assert losses[-1] < losses[0], losses
    for leaf in jax.tree.leaves(state.params):
        assert leaf.dtype == jnp.float32, leaf.dtype
    # Every non-scalar floating optimizer leaf is a moment tree — bfloat16.
    moments = [
        leaf for leaf in jax.tree.leaves(state.opt_state)
        if jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.ndim > 0
    ]
    assert moments, "no moment leaves found in opt_state"
    for leaf in moments:
        assert leaf.dtype == jnp.bfloat16, leaf.dtype


# ---------------------------------------------------------------------------
# Stochastic rounding (the bf16_full moment-store primitive)
# ---------------------------------------------------------------------------


def test_stochastic_round_exact_on_representable_values():
    xs = jnp.arange(-4.0, 4.0, 0.25, dtype=jnp.float32)
    for seed in (0, 1, 2):
        out = stochastic_round(xs, jax.random.PRNGKey(seed))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(xs.astype(jnp.bfloat16))
        )


def test_stochastic_round_neighbors_and_unbiased():
    # Between bf16(1.0) and bf16(1 + 1/128) (7 mantissa bits -> ulp 2^-7
    # at 1.0): must land on exactly those two neighbors with P(hi) equal to
    # the fractional distance, so the mean recovers x (RTN would pin every
    # sample to one side — that bias is what stalls moment EMAs).
    x = np.float32(1.0 + 1.0 / 512.0)
    lo, hi = np.float32(1.0), np.float32(1.0 + 1.0 / 128.0)
    keys = jax.random.split(jax.random.PRNGKey(7), 4096)
    vals = np.asarray(
        jax.vmap(lambda k: stochastic_round(jnp.float32(x), k))(keys)
    ).astype(np.float32)
    assert set(np.unique(vals)) == {lo, hi}
    assert abs(vals.mean() - x) < 0.1 * (hi - lo), vals.mean()


def test_stochastic_round_nonfinite_and_determinism():
    key = jax.random.PRNGKey(3)
    bad = jnp.array([np.nan, np.inf, -np.inf], dtype=jnp.float32)
    out = np.asarray(stochastic_round(bad, key)).astype(np.float32)
    assert np.isnan(out[0]) and out[1] == np.inf and out[2] == -np.inf

    x = jax.random.normal(jax.random.PRNGKey(4), (128,), jnp.float32)
    a = stochastic_round(x, key)
    b = stochastic_round(x, key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stochastic_round_rejects_non_bf16_target():
    with pytest.raises(NotImplementedError, match="bfloat16"):
        stochastic_round(
            jnp.ones(4), jax.random.PRNGKey(0), dtype=jnp.float16
        )


# ---------------------------------------------------------------------------
# HLO evidence: payloads actually halve
# ---------------------------------------------------------------------------


def test_stablehlo_dots_run_in_bf16():
    """The lowered (pre-XLA) program must matmul in bf16 — the MXU-rate
    half of the win. Read StableHLO, not compiled HLO: the CPU backend
    rewrites bf16 arithmetic to f32 during optimization."""
    mesh = helpers.mesh_of(dp=N)
    ds = _tokens(64)
    tr = _trainer(mesh, precision="bf16", vocab=64, max_len=32)
    tr.setup(ds.batch(0))
    bsh = batch_sharding(mesh)
    abs_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.asarray(x).shape, np.asarray(x).dtype, sharding=bsh
        ),
        dict(ds.batch(0)),
    )
    text = tr.train_step.lower(
        tr.abstract_state_with_shardings(), abs_batch
    ).as_text()
    dot_lines = [l for l in text.splitlines() if "dot_general" in l]
    assert dot_lines, "no dot_general in the lowered step"
    bf16_dots = [l for l in dot_lines if "bf16" in l]
    assert len(bf16_dots) >= 0.9 * len(dot_lines), (
        f"only {len(bf16_dots)}/{len(dot_lines)} dots are bf16"
    )


def test_grad_allreduce_wire_bytes_halve_plain_dp():
    mesh = helpers.mesh_of(dp=N)
    fp32_text = _hlo(mesh, spmd=True)
    bf16_text = _hlo(mesh, precision="bf16", spmd=True)
    assert "bf16[" in bf16_text
    ratio = (helpers.sync_wire_bytes(fp32_text, N)
             / helpers.sync_wire_bytes(bf16_text, N))
    # Grad sync is the only dp collective in the plain step: the ratio is
    # ~2 exactly (measured 1.99 — a few fp32 scalar reductions remain).
    assert 1.8 < ratio < 2.2, ratio


def test_zero1_param_gather_bytes_halve():
    mesh = helpers.mesh_of(dp=N)
    fp32_text = _hlo(mesh, spmd=True, zero1=True)
    bf16_text = _hlo(mesh, precision="bf16", spmd=True, zero1=True)
    ratio = (helpers.sync_wire_bytes(fp32_text, N)
             / helpers.sync_wire_bytes(bf16_text, N))
    # ZeRO-1 adds the param all-gather to the wire; with sharded fp32
    # masters the gathered compute copy is bf16 too (measured 1.91).
    assert 1.7 < ratio < 2.2, ratio


def test_zero1_bf16_full_cuts_resident_state_bytes_3x():
    """The ISSUE acceptance bar: per-member durable bytes (master params +
    optimizer state actually resident between steps) drop >= 3x under
    ZeRO-1 + bf16_full vs fp32. Analytic: 5 B/param (4 replicated + 8/N
    sharded) -> 1 B/param (4/N masters + 4/N moments); measured 5.0x."""
    mesh = helpers.mesh_of(dp=N)

    def member_bytes(precision):
        _, state = helpers.train_tiny_gpt2(
            mesh, n_steps=1, zero1=True,
            **({} if precision == "fp32"
               else dict(dtype=jnp.bfloat16, precision=precision)),
        )
        leaves = jax.tree.leaves(state.params) + [
            x for x in jax.tree.leaves(state.opt_state)
            if hasattr(x, "addressable_shards")
        ]
        return sum(x.addressable_shards[0].data.nbytes for x in leaves)

    fp32 = member_bytes("fp32")
    bf16 = member_bytes("bf16")
    full = member_bytes("bf16_full")
    assert fp32 / full >= 3.0, (fp32, full)
    assert fp32 / bf16 >= 2.5, (fp32, bf16)   # sharded masters: 5/1.5
    assert bf16 > full                         # bf16 moments shave more


# ---------------------------------------------------------------------------
# Checkpoints: masters are the durable schema, policy is not baked in
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrips_across_policies(tmp_path):
    mesh = helpers.mesh_of(dp=N)
    ds = _tokens()
    it = data_lib.sharded_batches(ds.iter_from(0), mesh)

    tr_b = _trainer(mesh, precision="bf16")
    sb = tr_b.init(0, ds.batch(0))
    for _ in range(2):
        sb, _ = tr_b.train_step(sb, next(it))
    with CheckpointManager(str(tmp_path / "b2f")) as ckpt:
        assert ckpt.save(2, sb, {"next_index": 2}, force=True)

    # bf16-saved -> fp32-restored: masters bit-exact, schema unchanged.
    tr_f = _trainer(mesh, precision="fp32")
    tr_f.init(9, ds.batch(0))
    with CheckpointManager(str(tmp_path / "b2f")) as ckpt:
        sf, data_state = ckpt.restore(tr_f.abstract_state_with_shardings())
    assert int(sf.step) == 2 and data_state["next_index"] == 2
    for a, b in zip(jax.tree.leaves(sb.params), jax.tree.leaves(sf.params)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sf, m = tr_f.train_step(sf, next(it))  # and it keeps training
    assert np.isfinite(float(m["loss"]))

    # fp32-saved -> bf16-restored (the migration direction).
    with CheckpointManager(str(tmp_path / "f2b")) as ckpt:
        assert ckpt.save(3, sf, {"next_index": 3}, force=True)
    tr_b2 = _trainer(mesh, precision="bf16")
    tr_b2.init(5, ds.batch(0))
    with CheckpointManager(str(tmp_path / "f2b")) as ckpt:
        sb2, _ = ckpt.restore(tr_b2.abstract_state_with_shardings())
    for a, b in zip(jax.tree.leaves(sf.params), jax.tree.leaves(sb2.params)):
        assert b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, m = tr_b2.train_step(sb2, next(it))
    assert np.isfinite(float(m["loss"]))


def test_corrupt_fallback_walks_under_bf16(tmp_path):
    # The PR-4 resilience path must not care about the active policy.
    mesh = helpers.mesh_of(dp=N)
    ds = _tokens()
    it = data_lib.sharded_batches(ds.iter_from(0), mesh)
    tr = _trainer(mesh, precision="bf16")
    state = tr.init(0, ds.batch(0))
    with CheckpointManager(str(tmp_path / "c")) as ckpt:
        for _ in range(2):
            state, _ = tr.train_step(state, next(it))
        assert ckpt.save(2, state, {"next_index": 2}, force=True)
        for _ in range(2):
            state, _ = tr.train_step(state, next(it))
        assert ckpt.save(4, state, {"next_index": 4}, force=True)
        ckpt.wait()
        assert ckpt.corrupt_latest_for_test() == 4

    tr2 = _trainer(mesh, precision="bf16")
    tr2.init(1, ds.batch(0))
    with CheckpointManager(str(tmp_path / "c")) as ckpt:
        s2, data_state = ckpt.restore(tr2.abstract_state_with_shardings())
    assert int(s2.step) == 2 and data_state["next_index"] == 2


# ---------------------------------------------------------------------------
# Composition: fused dispatch, compressed grads
# ---------------------------------------------------------------------------


def test_fused_k2_bitwise_parity_under_bf16():
    # The compute-copy cast sits INSIDE the scanned body, so fusing K steps
    # replays the exact same program: params must match bitwise.
    mesh = helpers.mesh_of(dp=4)
    ds = _tokens()

    def run(k, steps=4):
        tr = _trainer(mesh, precision="bf16")
        state = tr.init(0, ds.batch(0))
        if k == 1:
            it = data_lib.sharded_batches(ds.iter_from(0), mesh)
            for _ in range(steps):
                state, _ = tr.train_step(state, next(it))
        else:
            it = data_lib.sharded_superbatches(ds.iter_from(0), mesh, k)
            step = tr.fused_train_step(k)
            for _ in range(steps // k):
                state, _ = step(state, next(it))
        return state

    s1, s2 = run(1), run(2)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_grad_comm_keeps_fp32_residual_under_bf16():
    # bf16 grads are cast up INSIDE the shard_map body before the quantized
    # ring — otherwise ravel_pytree's dtype-restoring unravel would demote
    # the error-feedback residual and the summed grads to bf16.
    mesh = helpers.mesh_of(dp=N)
    plain, _ = helpers.train_tiny_gpt2(
        mesh, n_steps=4, dtype=jnp.bfloat16, precision="bf16"
    )
    lossy, state = helpers.train_tiny_gpt2(
        mesh, n_steps=4, dtype=jnp.bfloat16, precision="bf16",
        grad_comm="int8",
    )
    np.testing.assert_allclose(lossy, plain, atol=2e-2)
    for leaf in jax.tree.leaves(state.grad_residual):
        assert leaf.dtype == jnp.float32, leaf.dtype
