"""Fused multi-step dispatch (``train.steps_per_call``): K train steps per
compiled call via an on-device ``lax.scan`` over a stacked super-batch.

Contracts pinned here:
- K=1 is bit-identical to the unfused loop (it IS the unfused loop);
- K>1 matches K=1 step-for-step (final state + per-step metrics), including
  grad_accum>1 and (slow lane) a pipelined model — the scanned body is the
  same step function, so parity is exact up to scan-vs-unrolled compilation;
- every invalid steps_per_call cadence combination fails by name, up front;
- the logging path is non-blocking: ``DeferredMetrics`` emits interval n
  only at interval n+1's push (one-interval lag), and ``flush`` drains the
  tail so history is always complete.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.metrics import DeferredMetrics
from distributeddeeplearning_tpu.train import (
    FaultSpec,
    Trainer,
    check_fusion_cadences,
    fit,
    get_task,
    make_optimizer,
)

from helpers import mesh_of


def _tiny_gpt2(**kw):
    return models.get_model(
        "gpt2", size="tiny", vocab_size=256, max_len=64, dropout_rate=0.0,
        **kw,
    )


def _tokens(batch_size=16, seq_len=32):
    return data_lib.SyntheticTokens(
        batch_size=batch_size, seq_len=seq_len, vocab_size=256, seed=0,
        n_distinct=4,
    )


def _run(mesh, k, *, steps=8, model=None, ds=None, **trainer_kw):
    """Train ``steps`` steps in fused calls of size ``k``; returns the
    per-step losses and the final TrainState."""
    model = model or _tiny_gpt2()
    ds = ds or _tokens()
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        donate=False, **trainer_kw,
    )
    state = trainer.init(0, ds.batch(0))
    losses = []
    if k == 1:
        it = data_lib.sharded_batches(ds.iter_from(0), mesh)
        step = trainer.train_step
        for _ in range(steps):
            state, metrics = step(state, next(it))
            losses.append(float(metrics["loss"]))
    else:
        it = data_lib.sharded_superbatches(ds.iter_from(0), mesh, k)
        step = trainer.fused_train_step(k)
        for _ in range(steps // k):
            state, metrics = step(state, next(it))
            # stacked [K] per-step metrics — the fused observability contract
            losses.extend(float(v) for v in np.asarray(metrics["loss"]))
    return losses, state


def _assert_state_parity(s_a, s_b, rtol=2e-4, atol=1e-5):
    import jax

    for a, b in zip(jax.tree.leaves(s_a.params), jax.tree.leaves(s_b.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
        )
    assert int(s_a.step) == int(s_b.step)


def test_fused_parity_dp8():
    mesh = mesh_of(dp=8)
    losses_1, s1 = _run(mesh, 1)
    losses_4, s4 = _run(mesh, 4)
    assert len(losses_4) == len(losses_1) == 8
    np.testing.assert_allclose(losses_1, losses_4, rtol=2e-4, atol=1e-5)
    _assert_state_parity(s1, s4)


def test_fused_parity_grad_accum():
    mesh = mesh_of(dp=4)
    losses_1, s1 = _run(mesh, 1, steps=4, grad_accum=2)
    losses_2, s2 = _run(mesh, 2, steps=4, grad_accum=2)
    np.testing.assert_allclose(losses_1, losses_2, rtol=2e-4, atol=1e-5)
    _assert_state_parity(s1, s2)


@pytest.mark.slow
def test_fused_parity_pipelined_model():
    # The pipeline engine differentiates inside its own schedule; fusion
    # must scan THAT body unchanged. Slow lane: the K=1 pipeline parity is
    # already tier-1 via test_pipeline — this pins only fusion-on-top.
    mesh = mesh_of(dp=2, pp=2)
    model = models.get_model(
        "gpt2_pp", size="tiny", vocab_size=256, max_len=64,
        num_stages=2, num_microbatches=2, mesh=mesh,
        schedule="1f1b_interleaved",
    )
    ds = _tokens(batch_size=8)
    losses_1, s1 = _run(mesh, 1, steps=4, model=model, ds=ds)
    losses_2, s2 = _run(mesh, 2, steps=4, model=model, ds=ds)
    np.testing.assert_allclose(losses_1, losses_2, rtol=2e-4, atol=1e-5)
    _assert_state_parity(s1, s2)


def test_steps_per_call_1_is_bit_identical():
    # K=1 must not even go through the fused wrapper: fused_train_step(1)
    # IS train_step, so the compiled program is the same object.
    mesh = mesh_of(dp=4)
    model = _tiny_gpt2()
    ds = _tokens()
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        donate=False,
    )
    trainer.init(0, ds.batch(0))
    assert trainer.fused_train_step(1) is trainer.train_step

    # And fit(steps_per_call=1) produces bitwise-equal params to the direct
    # step loop over the same batches.
    import jax

    state_a = trainer.init(0, ds.batch(0))
    state_b = trainer.init(0, ds.batch(0))
    state_a, _ = fit(
        trainer, state_a, data_lib.sharded_batches(ds.iter_from(0), mesh),
        steps=4, log_every=2, steps_per_call=1, log_fn=lambda m: None,
    )
    it = data_lib.sharded_batches(ds.iter_from(0), mesh)
    for _ in range(4):
        state_b, _ = trainer.train_step(state_b, next(it))
    for a, b in zip(
        jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)
    ):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_fit_runs_fused_and_history_is_complete():
    mesh = mesh_of(dp=4)
    model = _tiny_gpt2()
    ds = _tokens()
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
        donate=False,
    )
    state = trainer.init(0, ds.batch(0))
    lines = []
    state, history = fit(
        trainer, state,
        data_lib.sharded_superbatches(ds.iter_from(0), mesh, 2),
        steps=8, log_every=2, steps_per_call=2, log_fn=lines.append,
    )
    assert int(state.step) == 8
    # Deferred fetch must not drop lines: every boundary present, in order.
    assert [h["step"] for h in history] == [2, 4, 6, 8]
    assert lines == history
    assert all(np.isfinite(h["loss"]) for h in history)


@pytest.mark.parametrize(
    "kwargs, match",
    [
        (dict(steps=7), "divide steps=7"),
        (dict(steps=8, log_every=3), "divide log_every=3"),
        (dict(steps=8, eval_every=5), "divide eval_every=5"),
        (dict(steps=8, save_every=5), "divide save_every=5"),
        (dict(steps=8, fault=FaultSpec("step", 3)), "divide fault_step=3"),
        (dict(steps=8, fault=FaultSpec("hang", 3)), "divide fault_step=3"),
        (dict(steps=8, fault=FaultSpec("corrupt", 3)), "divide fault_step=3"),
        (dict(steps=8, fault=FaultSpec("bogus", 2)), "not in"),
        (dict(steps=8, start=3), "resume step 3"),
    ],
)
def test_fusion_cadence_fences(kwargs, match):
    with pytest.raises(ValueError, match=match):
        check_fusion_cadences(2, **kwargs)


def test_fusion_cadence_fence_k0():
    with pytest.raises(ValueError, match="steps_per_call=0"):
        check_fusion_cadences(0, steps=8)


def test_fusion_cadence_nan_fault_exempt():
    # nan:K is compiled INTO the step body (it fires mid-scan on device), so
    # it composes with any fused cadence — unlike the host-side kinds.
    check_fusion_cadences(2, steps=8, fault=FaultSpec("nan", 3))
    # Kind validation still applies at k=1 (the unfused loop).
    with pytest.raises(ValueError, match="not in"):
        check_fusion_cadences(1, steps=8, fault=FaultSpec("bogus", 3))


def test_fit_rejects_bad_cadence_before_stepping():
    # The fence must fire before any batch is consumed or step dispatched —
    # trainer/batches are never touched, so sentinels suffice.
    class Boom:
        def __iter__(self):
            raise AssertionError("batches consumed despite fence")

    fake_state = type("S", (), {"step": 0})()
    with pytest.raises(ValueError, match="divide log_every"):
        fit(None, fake_state, Boom(), steps=8, log_every=3, steps_per_call=2)


def test_cli_fences_bad_steps_per_call_cheaply():
    from distributeddeeplearning_tpu.cli import cmd_train
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = apply_overrides(
        load_config("configs/resnet18_cifar10.py"),
        ["train.steps=10", "train.steps_per_call=4"],
    )
    with pytest.raises(ValueError, match="divide steps=10"):
        cmd_train(cfg)


def test_deferred_metrics_one_interval_lag():
    import jax.numpy as jnp

    emitted = []
    d = DeferredMetrics(emitted.append)
    d.push(10, {"loss": jnp.float32(1.0)}, wall_s=0.5)
    # One-interval lag: nothing emitted until the NEXT boundary arrives.
    assert emitted == []
    d.push(20, {"loss": jnp.float32(2.0)}, wall_s=0.7)
    assert [m["step"] for m in emitted] == [10]
    assert emitted[0] == {"loss": 1.0, "step": 10, "wall_s": 0.5}
    d.flush()
    assert [m["step"] for m in emitted] == [10, 20]
    assert emitted[1]["loss"] == 2.0
    d.flush()  # idempotent — nothing pending
    assert len(emitted) == 2


def test_stacked_batches_shapes_and_tail():
    ds = _tokens(batch_size=4, seq_len=8)
    groups = list(data_lib.stacked_batches(
        (ds.batch(i) for i in range(7)), 3
    ))
    # 7 batches at K=3 -> 2 full groups, partial tail dropped.
    assert len(groups) == 2
    assert groups[0]["tokens"].shape == (3, 4, 9)
    np.testing.assert_array_equal(groups[0]["tokens"][1], ds.batch(1)["tokens"])


def test_superbatch_sharding_places_batch_dim():
    mesh = mesh_of(dp=4)
    ds = _tokens(batch_size=8, seq_len=8)
    sb = next(data_lib.sharded_superbatches(ds.iter_from(0), mesh, 2))
    arr = sb["tokens"]
    assert arr.shape == (2, 8, 9)
    spec = arr.sharding.spec
    # scan dim replicated, batch dim over (dp, fsdp)
    assert spec[0] is None and tuple(spec[1]) == ("dp", "fsdp")


def test_prefetch_size_threaded_from_config(monkeypatch):
    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    seen = {}
    real_prefetch = data_lib.prefetch

    def spy(it, size=2):
        seen["size"] = size
        return real_prefetch(it, size)

    monkeypatch.setattr(cli.data_lib, "prefetch", spy)
    cfg = apply_overrides(
        load_config("configs/resnet18_cifar10.py"),
        ["data.batch_size=8", "data.image_size=8",
         'model.kwargs={"num_classes":10,"width":8,"stem":"cifar"}',
         "train.steps=2", "train.log_every=0", "data.prefetch_size=3"],
    )
    assert cli.cmd_train(cfg) == 0
    assert seen["size"] == 3


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_PROBE = (
    "import json, jax;"
    "from distributeddeeplearning_tpu.utils.compat import setup_compile_cache;"
    "print(json.dumps([setup_compile_cache(),"
    " jax.config.jax_compilation_cache_dir]))"
)


@pytest.mark.parametrize("placed", ["set", "unset"])
def test_compile_cache_is_placed_from_outside(tmp_path, placed):
    """THE rule (utils.compat.setup_compile_cache): where
    JAX_COMPILATION_CACHE_DIR is set the cache lives there and no directory
    is set in code; unset, it lives at <checkout>/.jax_cache — never at a
    path built from a temporary name, a pid or the time."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(_REPO, ".jax_cache")
    if placed == "set":
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [want, want]


def test_second_process_finds_what_the_first_compiled(tmp_path):
    """Two `cli train` processes on one JAX_COMPILATION_CACHE_DIR: the first
    fills it, the second compiles the same tiny step and adds no entry."""
    cache = tmp_path / "cc"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    cmd = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli", "train",
        "--config", "configs/resnet18_cifar10.py",
        "--override", "data.batch_size=8", "--override", "data.image_size=8",
        "--override",
        'model.kwargs={"num_classes":10,"width":8,"stem":"cifar"}',
        "--override", "train.steps=2", "--override", "train.log_every=0",
    ]

    def entries():
        return sorted(p.name for p in cache.iterdir()
                      if p.name.endswith("-cache"))

    for attempt in ("first", "second"):
        out = subprocess.run(
            cmd, env=env, cwd=_REPO, capture_output=True, text=True,
            timeout=540,
        )
        assert out.returncode == 0, (attempt, out.stderr[-2000:])
        if attempt == "first":
            filled = entries()
            assert filled, "the first process persisted nothing"
    assert entries() == filled
