"""Evaluation path (VERDICT round-1 missing #3): ``eval_every`` drives a
real eval loop inside ``fit``, ``evaluate`` reports top-1 accuracy for the
vision tasks (``BASELINE.json:2`` "top-1 parity"), and the ``eval`` CLI
subcommand works standalone.
"""

import json

import pytest

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.cli import cmd_eval, cmd_train, make_eval_fn
from distributeddeeplearning_tpu.config import (
    Config,
    DataConfig,
    ModelConfig,
    OptimConfig,
    TrainConfig,
)
from distributeddeeplearning_tpu.train import (
    Trainer,
    evaluate,
    fit,
    get_task,
    make_optimizer,
)


def _trainer_and_data(mesh, batch_size=32):
    model = models.get_model("resnet18", num_classes=10, width=8)
    trainer = Trainer(
        model, make_optimizer("sgd", 0.05, momentum=0.9),
        get_task("classification"), mesh, donate=False,
    )
    ds = data_lib.SyntheticImages(
        batch_size=batch_size, image_size=16, num_classes=10, n_distinct=4
    )
    return trainer, ds


def test_evaluate_reports_top1_accuracy(mesh8):
    import itertools

    trainer, ds = _trainer_and_data(mesh8)
    state = trainer.init(0, ds.batch(0))
    batches = data_lib.sharded_batches(
        itertools.islice(ds.iter_from(0), 4), mesh8
    )
    metrics = evaluate(trainer, state, batches)
    assert set(metrics) >= {"eval_loss", "eval_accuracy"}
    assert 0.0 <= metrics["eval_accuracy"] <= 1.0


def test_eval_accuracy_rises_during_fit(mesh8):
    # Memorizable set (n_distinct=4): training must drive eval accuracy up.
    trainer, ds = _trainer_and_data(mesh8)
    state = trainer.init(0, ds.batch(0))

    def eval_fn():
        it = ds.iter_from(0)
        return data_lib.sharded_batches(
            (next(it) for _ in range(4)), mesh8
        )

    _, history = fit(
        trainer, state, data_lib.sharded_batches(ds.iter_from(0), mesh8),
        steps=24, log_every=0, eval_every=8, eval_fn=eval_fn,
    )
    evals = [h for h in history if "eval_accuracy" in h]
    assert len(evals) == 3, history
    assert evals[-1]["eval_accuracy"] > evals[0]["eval_accuracy"], evals
    assert evals[-1]["eval_loss"] < evals[0]["eval_loss"], evals


def test_fit_rejects_eval_every_without_eval_fn(mesh8):
    trainer, ds = _trainer_and_data(mesh8)
    state = trainer.init(0, ds.batch(0))
    with pytest.raises(ValueError, match="eval_fn"):
        fit(
            trainer, state,
            data_lib.sharded_batches(ds.iter_from(0), mesh8),
            steps=2, eval_every=1,
        )


def _tiny_cfg(**train_kw):
    return Config(
        model=ModelConfig(name="resnet18", kwargs={"num_classes": 10, "width": 8}),
        data=DataConfig(
            kind="synthetic_image", batch_size=16, image_size=16, n_distinct=4
        ),
        optim=OptimConfig(name="sgd", lr=0.05),
        train=TrainConfig(task="classification", **train_kw),
    )


def test_cmd_train_emits_eval_lines(capsys):
    cfg = _tiny_cfg(steps=4, log_every=0, eval_every=2, eval_batches=2)
    assert cmd_train(cfg) == 0
    evals = [
        json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("{") and "eval_accuracy" in line
    ]
    assert len(evals) == 2 and all("eval_loss" in e for e in evals)


def test_cmd_eval_standalone(capsys):
    cfg = _tiny_cfg(steps=0, eval_batches=2)
    assert cmd_eval(cfg) == 0
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    assert any("eval_accuracy" in m for m in lines)


def test_eval_seed_selects_heldout_split(mesh8):
    cfg = _tiny_cfg()
    cfg = Config(
        model=cfg.model,
        data=DataConfig(
            kind="synthetic_image", batch_size=16, image_size=16,
            n_distinct=4, eval_seed=123,
        ),
        optim=cfg.optim,
        train=cfg.train,
    )
    train_kw = cfg.data.dataset_kwargs()
    eval_kw = cfg.data.eval_dataset_kwargs()
    assert train_kw["seed"] == 0 and eval_kw["seed"] == 123
    ds_a = data_lib.make_dataset(cfg.data.kind, **train_kw)
    ds_b = data_lib.make_dataset(cfg.data.kind, **eval_kw)
    assert not (ds_a.batch(0)["image"] == ds_b.batch(0)["image"]).all()


def test_eval_accumulates_fp32_under_bf16_model(mesh8):
    # Mixed-precision satellite (docs/MIXED_PRECISION.md): a bf16-compute
    # model must not leak bf16 into metric accumulation — eval_step pins
    # its outputs to fp32, and evaluate()'s on-device sums stay fp32, so a
    # long eval pass cannot lose counts to bf16's 8-bit mantissa.
    import itertools

    import jax
    import jax.numpy as jnp

    model = models.get_model(
        "gpt2", size="tiny", vocab_size=256, max_len=64, dropout_rate=0.0,
        dtype=jnp.bfloat16,
    )
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3, precision="bf16"),
        get_task("lm"), mesh8, donate=False, precision="bf16",
    )
    ds = data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=256, seed=0, n_distinct=4
    )
    state = trainer.init(0, ds.batch(0))
    batch = next(data_lib.sharded_batches(ds.iter_from(0), mesh8))
    for v in jax.tree.leaves(trainer.eval_step(state, batch)):
        if jnp.issubdtype(v.dtype, jnp.inexact):
            assert v.dtype == jnp.float32, v.dtype
    metrics = evaluate(
        trainer, state,
        data_lib.sharded_batches(itertools.islice(ds.iter_from(0), 4), mesh8),
    )
    import numpy as np

    assert np.isfinite(metrics["eval_loss"])


def test_evaluate_single_host_pull_per_pass(mesh8, monkeypatch):
    # Metric sums accumulate on device; the whole pass costs exactly ONE
    # jax.device_get, regardless of batch count (the old loop pulled
    # batches x metrics scalars, serializing eval on host round-trips).
    import itertools

    import jax

    from distributeddeeplearning_tpu import train as train_mod

    trainer, ds = _trainer_and_data(mesh8)
    state = trainer.init(0, ds.batch(0))
    batches = list(data_lib.sharded_batches(
        itertools.islice(ds.iter_from(0), 6), mesh8
    ))

    pulls = []
    real_device_get = jax.device_get

    def spy(tree):
        pulls.append(tree)
        return real_device_get(tree)

    monkeypatch.setattr(train_mod.jax, "device_get", spy)
    metrics = evaluate(trainer, state, iter(batches))
    assert len(pulls) == 1, f"expected 1 host pull, saw {len(pulls)}"
    assert 0.0 <= metrics["eval_accuracy"] <= 1.0
