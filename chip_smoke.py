#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` needs one TPU chip and drives the main path once,
through the entry points a user would call, at the published widths of
GPT-2 124M (and ResNet-50 as the conv control): a trainer that takes a few
steps (``cli train``), a server that answers a few requests
(``ServingEngine`` as ``cli serve`` builds it), and every Pallas kernel of
the main path against its plain reference. ``--chips 4`` runs only the
data-parallel trainer on four chips and the same job on one of them.

One process, one ``import jax``. Each phase prints one JSON line; a phase
that fails raises, so the exit code is non-zero and no result line is
printed. Weights and data are random, made from ``--seed``. The last line
of stdout is ``{"ok": true, "device": {...}}`` with the device as jax
reports it. With no accelerator the script fails at its first check.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
GPT2_CONFIG = os.path.join(_REPO, "configs", "gpt2_owt.py")
RESNET_CONFIG = os.path.join(_REPO, "configs", "resnet50_imagenet.py")

# bf16 serving vs the bf16 full-forward reference on random weights: logits
# have std ~0.55 (top ~2.3) and sit on the bf16 grid (spacing 1/64 there),
# so near-ties flip; a wrong mask, position or page would miss by ~1.
SERVE_LOGIT_TOL = 0.125
# Same global batch on 4 chips vs 1: per-shard flash/reduce order differs.
DP_LOSS_TOL = 0.02
# Flash backward vs the reference's gradients, as a share of the largest
# reference entry: bf16 gradients, so ~2 ulps there (interpret mode: 5e-3).
FLASH_BWD_REL_TOL = 2e-2


def device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def check(cond, msg) -> None:
    """Phase checks raise (never ``assert``: ``-O`` must not pass a run)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class CompileLog:
    """Every backend compile jax performs, by jitted function name, from
    jax's own monitoring events — observed from outside the program."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            self.events.append((kw.get("fun_name", "?"), float(duration)))

    def since(self, mark: int):
        return self.events[mark:]

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def peak_bytes() -> int | None:
    """Highest ``peak_bytes_in_use`` over local devices (a process-lifetime
    high-water mark), or None on the CPU backend."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def emit(phase: str, t0: float, compiles, **checked) -> dict:
    rec = {
        "phase": phase,
        "seconds": round(time.perf_counter() - t0, 2),
        "compile_seconds": round(sum(d for _, d in compiles), 2),
        "peak_bytes_in_use": peak_bytes(),
        **checked,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# train: `python -m distributeddeeplearning_tpu.cli train`, in process
# ---------------------------------------------------------------------------


def run_cli_train(config: str, overrides: list[str]) -> list[dict]:
    """``cli.main(["train", ...])`` with its stdout captured; returns the
    JSON metric lines it printed (one per logged step)."""
    from distributeddeeplearning_tpu import cli

    argv = ["train", "--config", config]
    for o in overrides:
        argv += ["--override", o]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    check(rc == 0, f"cli train exited {rc}:\n{buf.getvalue()[-2000:]}")
    lines = []
    for line in buf.getvalue().splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            if "loss" in rec:
                lines.append(rec)
    return lines


def compiled_train_step_text(config: str, overrides: list[str],
                             devices=None) -> str:
    """The optimized HLO of the train step this config runs: built by the
    same ``build_all``, lowered on abstract operands (nothing materialized),
    compiled through the persistent cache the run just filled."""
    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = apply_overrides(load_config(config), overrides)
    _, _, trainer, dataset = cli.build_all(cfg, devices=devices)
    return trainer.lower_train_step(dataset.batch(0)).compile().as_text()


def check_losses(losses, *, steps, first_loss, must_fall=True):
    """Finite; the first within 0.5 of ln(classes) — random init, so a wrong
    head or reduction shows; and (given steps enough to show it) falling."""
    check(len(losses) == steps, f"{len(losses)} loss lines for {steps} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(
        abs(losses[0] - first_loss) <= 0.5,
        f"first loss {losses[0]:.4f} not within 0.5 of {first_loss:.4f} "
        "(random init)",
    )
    if must_fall:
        check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def phase_train(name: str, config: str, overrides: list[str], *, steps: int,
                warmup_steps: int, first_loss: float, expect_kernels: bool,
                compiles: CompileLog) -> dict:
    """``steps`` steps of ``cli train`` on ``config``; the configs' warm-ups
    (200 and 500 steps) are cut so the loss has to fall inside the run."""
    t0 = time.perf_counter()
    mark = len(compiles.events)
    overrides = [
        *overrides, f"train.steps={steps}", "train.log_every=1",
        f"optim.warmup_steps={warmup_steps}",
    ]
    lines = run_cli_train(config, overrides)
    ran = compiles.since(mark)
    losses = [float(m["loss"]) for m in lines]
    check_losses(losses, steps=steps, first_loss=first_loss)
    step_compiles = [d for n, d in ran if n == "jit(step_fn)"]
    check(
        len(step_compiles) == 1,
        f"train_step compiled {len(step_compiles)} times, want 1",
    )
    t_inspect = time.perf_counter()
    text = compiled_train_step_text(config, overrides)
    t_inspect = time.perf_counter() - t_inspect
    n_kernels = text.count("tpu_custom_call")
    if expect_kernels:
        check(n_kernels > 0, "no tpu_custom_call in the compiled train step")
    return emit(
        name, t0, ran,
        steps=steps, losses=[round(x, 4) for x in losses],
        first_loss_expected=round(first_loss, 4),
        train_step_compiles=len(step_compiles),
        train_step_compile_seconds=round(step_compiles[0], 2),
        inspection_compile_seconds=round(t_inspect, 2),
        tpu_custom_calls=n_kernels,
        overrides=overrides,
    )


# ---------------------------------------------------------------------------
# serve: ServingEngine as cmd_serve builds it, token-id prompts
# ---------------------------------------------------------------------------


def serve_requests(engine, prompts, max_new_tokens):
    from distributeddeeplearning_tpu.serving import Request

    for p in prompts:
        engine.submit(Request(prompt=list(p), max_new_tokens=max_new_tokens))
    done = engine.run()
    check(
        len(done) == len(prompts),
        f"{len(done)} of {len(prompts)} requests finished",
    )
    by_prompt = {tuple(st.request.prompt): list(st.generated) for st in done}
    out = [by_prompt[tuple(p)] for p in prompts]
    check(
        all(len(g) == max_new_tokens for g in out),
        f"generated lengths {[len(g) for g in out]} != {max_new_tokens}",
    )
    return out


def compare_with_full_forward(model, params, prompts, generated, *, tol):
    """Every served token must be the full-forward reference's argmax on the
    same params and device, or within ``tol`` of it in the reference's own
    logits (``generate.greedy_agreement``, teacher-forced)."""
    from distributeddeeplearning_tpu.generate import greedy_agreement

    rec = greedy_agreement(model, params, prompts, generated)
    check(
        rec["worst_logit_gap"] <= tol,
        f"a served token is {rec['worst_logit_gap']:.4f} below the "
        f"full-forward argmax (tolerance {tol})",
    )
    rec["worst_logit_gap"] = round(rec["worst_logit_gap"], 4)
    rec["logit_tol"] = tol
    return rec


def phase_serve(config: str, overrides: list[str], *, prompt_lens,
                max_new_tokens: int, kernels, tol: float, seed: int,
                expect_kernels: bool, compiles: CompileLog) -> dict:
    import numpy as np

    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.config import apply_overrides, load_config
    from distributeddeeplearning_tpu.serving import (
        ServingEngine,
        check_serving_composition,
    )
    from distributeddeeplearning_tpu.telemetry import Telemetry

    t0 = time.perf_counter()
    mark = len(compiles.events)
    cfg = apply_overrides(load_config(config), overrides)
    check_serving_composition(cfg)
    with contextlib.redirect_stdout(io.StringIO()):  # "no checkpoint" line
        mesh, model, trainer, dataset = cli.build_all(cfg)
        model, state = cli.serving_model_and_state(
            cfg, model, trainer, dataset
        )
    params = state.params
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, model.vocab_size, n).tolist() for n in prompt_lens
    ]

    def engine_for(kernel):
        scfg = dataclasses.replace(cfg.serving, attn_kernel=kernel)
        eng = ServingEngine(
            model, params, scfg, seed=seed,
            telemetry=Telemetry.from_config(cfg),
        )
        eng.warmup()
        return eng

    results = {}
    for kernel in kernels:
        k_mark = len(compiles.events)
        engine = engine_for(kernel)
        cold = compiles.since(k_mark)
        warmed = engine.num_compiles
        generated = serve_requests(engine, prompts, max_new_tokens)
        check(
            engine.num_compiles == warmed,
            f"attn_kernel={kernel}: {engine.num_compiles - warmed} "
            "executables compiled after warmup",
        )
        rec = compare_with_full_forward(
            model, params, prompts, generated, tol=tol
        )
        # Each path is demanded by name (undemanded, the engine would
        # choose the kernel on the chip and be compared with itself).
        want = "in_place" if kernel == "pallas" else "gather"
        path = engine.stats()["read_path"]
        check(
            path == want,
            f"attn_kernel={kernel}: the engine reads {path}, not {want}",
        )
        if expect_kernels:
            check(
                ("tpu_custom_call"
                 in engine._decode_exe_or_compile().as_text())
                == (kernel == "pallas"),
                f"attn_kernel={kernel}: the decode executable's "
                "tpu_custom_call does not match the path asked for",
            )
        rec["executables"] = engine.num_compiles
        rec["compile_seconds"] = round(sum(d for _, d in cold), 2)
        st = engine.scheduler.stats()
        rec["pool_blocks"] = st["free_blocks"] + st["used_blocks"]
        del engine
        # A second engine in the same process finds every donated
        # prefill/decode executable in the persistent cache: its tokens
        # must be the first engine's (serving warm-starts from the cache).
        w_mark = len(compiles.events)
        again = serve_requests(engine_for(kernel), prompts, max_new_tokens)
        check(
            again == generated,
            f"attn_kernel={kernel}: a cache-warm engine served other tokens",
        )
        rec["warm_compile_seconds"] = round(
            sum(d for _, d in compiles.since(w_mark)), 2
        )
        results[kernel] = rec
    return emit(
        "serve_gpt2", t0, compiles.since(mark),
        prompt_lens=list(prompt_lens), max_new_tokens=max_new_tokens,
        vocab_size=model.vocab_size, kernels=results, overrides=overrides,
    )


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel of the main path vs its plain reference
# ---------------------------------------------------------------------------


def _max_err(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)
    )))


def check_flash_backward(grads, ref_grads) -> float:
    """dq, dk, dv against the reference's, each RELATIVE to its largest
    reference entry. The loss is a mean over N outputs, so every entry is
    ~1/N: an absolute bound that suits one size passes anything, all zeros
    included, at a larger one."""
    import jax.numpy as jnp

    err = max(
        _max_err(g, r) / float(jnp.max(jnp.abs(r.astype(jnp.float32))))
        for g, r in zip(grads, ref_grads)
    )
    check(err < FLASH_BWD_REL_TOL,
          f"flash bwd err {err} of the largest reference gradient")
    return err


def phase_kernels(*, heads: int, head_dim: int, seq: int, pool_blocks: int,
                  vocab: int, embed: int, llama_kwargs: dict, interpret,
                  seed: int, compiles: CompileLog) -> dict:
    """``interpret`` is False on the chip (the kernels themselves, compiled);
    the CPU rehearsal passes None, which resolves to interpret mode there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributeddeeplearning_tpu import models
    from distributeddeeplearning_tpu.comms_quant import block_quantize
    from distributeddeeplearning_tpu.data import (
        SyntheticTokens,
        sharded_batches,
    )
    from distributeddeeplearning_tpu.mesh import single_device_mesh
    from distributeddeeplearning_tpu.ops import (
        attention_reference,
        flash_attention,
        fused_adamw,
        paged_attention,
        paged_attention_reference,
        ring_attention_pallas,
    )
    from distributeddeeplearning_tpu.ops.fused_adamw import decay_leaf
    from distributeddeeplearning_tpu.train import (
        Trainer,
        get_task,
        make_optimizer,
    )

    t0 = time.perf_counter()
    mark = len(compiles.events)
    key = jax.random.PRNGKey(seed)
    errs = {}

    # flash forward + backward, causal, bf16
    qkv = [
        jax.random.normal(k, (2, seq, heads, head_dim), jnp.bfloat16)
        for k in jax.random.split(key, 3)
    ]

    def sq_mean(attn):
        return lambda q, k, v: jnp.mean(attn(q, k, v).astype(jnp.float32) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=True)

    errs["flash_fwd"] = _max_err(jax.jit(flash)(*qkv), jax.jit(ref)(*qkv))
    check(errs["flash_fwd"] < 0.05, f"flash fwd err {errs['flash_fwd']}")
    g = jax.jit(jax.grad(sq_mean(flash), argnums=(0, 1, 2)))(*qkv)
    gr = jax.jit(jax.grad(sq_mean(ref), argnums=(0, 1, 2)))(*qkv)
    flash_bwd_rel_err = check_flash_backward(g, gr)

    # ring attention (Pallas) at cp=1: the fused per-visit block kernel
    mesh = single_device_mesh()
    ring = jax.jit(lambda q, k, v: ring_attention_pallas(
        q, k, v, mesh, causal=True, interpret=interpret
    ))(*qkv)
    errs["ring_pallas_cp1"] = _max_err(ring, jax.jit(ref)(*qkv))
    check(errs["ring_pallas_cp1"] < 0.05,
          f"ring-pallas err {errs['ring_pallas_cp1']}")

    # paged decode attention, fp and int8, MHA (GPT-2) and GQA (Llama) folds
    B, BS = 8, 16
    pages = max(2, min(64, pool_blocks // B))
    for tag, G, R in (("mha", heads, 1), ("gqa", max(1, heads // 2), 2)):
        ks = jax.random.split(jax.random.fold_in(key, G), 3)
        q = jax.random.normal(ks[0], (B, G * R, head_dim), jnp.bfloat16)
        # as the engine stores them: heads folded into the minor dim
        pk = jax.random.normal(ks[1], (pool_blocks, BS, G * head_dim))
        pv = jax.random.normal(ks[2], (pool_blocks, BS, G * head_dim))
        rng = np.random.default_rng(seed)
        table = jnp.asarray(
            rng.permutation(pool_blocks)[: B * pages].reshape(B, pages),
            jnp.int32,
        )
        # mixed cursors: an idle row (0), a page boundary, a full row
        lens = jnp.asarray(
            [0, BS - 1, BS, pages * BS - 1]
            + list(rng.integers(1, pages * BS, B - 4)), jnp.int32,
        )
        fp = jax.jit(lambda *a: paged_attention(
            *a, num_rep=R, interpret=interpret
        ))(q, pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16), table, lens)
        fp_ref = jax.jit(lambda *a: paged_attention_reference(
            *a, num_rep=R
        ))(q, pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16), table, lens)
        errs[f"paged_fp_{tag}"] = _max_err(fp, fp_ref)
        check(errs[f"paged_fp_{tag}"] < 0.05,
              f"paged fp {tag} err {errs[f'paged_fp_{tag}']}")
        qk, sk = block_quantize(pk.reshape(-1), head_dim)
        qv, sv = block_quantize(pv.reshape(-1), head_dim)
        qk, qv = qk.reshape(pk.shape), qv.reshape(pv.shape)
        sk, sv = (s.reshape(pool_blocks, BS, G) for s in (sk, sv))
        q8 = jax.jit(lambda *a: paged_attention(
            *a[:5], scale_k=a[5], scale_v=a[6], num_rep=R,
            interpret=interpret,
        ))(q, qk, qv, table, lens, sk, sv)
        q8_ref = jax.jit(lambda *a: paged_attention_reference(
            *a[:5], scale_k=a[5], scale_v=a[6], num_rep=R,
        ))(q, qk, qv, table, lens, sk, sv)
        errs[f"paged_int8_{tag}"] = _max_err(q8, q8_ref)
        check(errs[f"paged_int8_{tag}"] < 0.05,
              f"paged int8 {tag} err {errs[f'paged_int8_{tag}']}")

    # fused AdamW vs optax.adamw over GPT-2-shaped leaves
    params = {
        "wte": 0.02 * jax.random.normal(key, (vocab, embed)),
        "qkv": 0.02 * jax.random.normal(key, (embed, 3 * embed)),
        "bias": jnp.zeros((embed,)),
        "odd": jnp.ones((7,)),
    }
    grads = jax.tree.map(lambda p: 0.1 * jnp.cos(p * 37.0), params)
    tx = fused_adamw(1e-2, weight_decay=0.01, interpret=interpret)
    rx = optax.adamw(
        1e-2, weight_decay=0.01, mask=lambda t: jax.tree.map(decay_leaf, t)
    )

    def two_steps(t):
        @jax.jit
        def step(p, s, g):  # grads as an operand, not a 150 MB constant
            du, s = t.update(g, s, p)
            return optax.apply_updates(p, du), s

        p, s = step(params, t.init(params), grads)
        return step(p, s, grads)[0]

    p1, p2 = two_steps(tx), two_steps(rx)
    errs["fused_adamw"] = max(_max_err(p1[k], p2[k]) for k in params)
    check(errs["fused_adamw"] < 1e-5, f"fused adamw err {errs['fused_adamw']}")

    # one whole train step of the modern-decoder path: RoPE + GQA + SwiGLU
    # through the flash kernel and the chunked head
    model = models.get_model(
        "llama", attn_impl="flash", chunked_head=True, dtype=jnp.bfloat16,
        **llama_kwargs,
    )
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm", head_chunk=128),
        mesh, donate=False,
    )
    ds = SyntheticTokens(
        batch_size=4, seq_len=model.max_len, vocab_size=model.vocab_size,
        seed=seed,
    )
    state = trainer.init(seed, ds.batch(0))
    batch = next(iter(sharded_batches(ds.iter_from(0), mesh)))
    _, metrics = trainer.train_step(state, batch)
    loss = float(metrics["loss"])
    check(
        abs(loss - math.log(model.vocab_size)) < 1.0,
        f"llama step loss {loss} far from ln(vocab)",
    )
    return emit(
        "kernels", t0, compiles.since(mark),
        heads=heads, head_dim=head_dim, seq=seq, pool_blocks=pool_blocks,
        max_abs_err={k: float(f"{v:.3g}") for k, v in errs.items()},
        flash_bwd_rel_err=float(f"{flash_bwd_rel_err:.3g}"),
        llama_step_loss=round(loss, 4),
    )


# ---------------------------------------------------------------------------
# four chips: data-parallel ZeRO-1 training vs the same job on one chip
# ---------------------------------------------------------------------------


def _dp_run(config, overrides, devices, steps):
    """``steps`` train steps of ``config`` on a mesh over ``devices``, built
    by ``cli.build_all``; returns (losses, final state, trainer)."""
    import jax

    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu import data as data_lib
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    cfg = apply_overrides(load_config(config), overrides)
    mesh, _, trainer, dataset = cli.build_all(cfg, devices=devices)
    state = trainer.init(cfg.train.seed, dataset.batch(0))
    losses = []
    batches = data_lib.sharded_batches(dataset.iter_from(0), mesh)
    for _, batch in zip(range(steps), batches):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    jax.block_until_ready(state)
    return losses, state, trainer


def phase_dp(config: str, overrides: list[str], *, steps: int, n_chips: int,
             loss_tol: float, first_loss: float, expect_kernels: bool,
             compiles: CompileLog) -> dict:
    """The config on ``n_chips`` devices (dp=n, ZeRO-1, flash under
    shard_map) and, in the same process, the same config, seed and global
    batch on ``jax.devices()[:1]``."""
    import jax

    t0 = time.perf_counter()
    mark = len(compiles.events)
    devices = jax.devices()
    check(len(devices) >= n_chips, f"{len(devices)} devices, need {n_chips}")
    devices = devices[:n_chips]
    overrides = [*overrides, "optim.warmup_steps=0"]
    losses_n, state, trainer = _dp_run(config, overrides, devices, steps)

    # params replicated, ZeRO-1 moments sharded 1/n, on n distinct devices
    big = max(jax.tree.leaves(state.params), key=lambda x: x.size)
    check(
        len({s.device for s in big.addressable_shards}) == n_chips,
        "params not addressable on every device",
    )
    moments = [
        x for x in jax.tree.leaves(state.opt_state)
        if hasattr(x, "shape") and x.shape == big.shape
    ]
    check(moments, "no optimizer moment matches the largest param")
    for m in moments:
        shards = m.addressable_shards
        check(
            len({s.device for s in shards}) == n_chips,
            "ZeRO-1 moment shards not on distinct devices",
        )
        check(
            all(math.prod(s.data.shape) * n_chips == m.size for s in shards),
            f"ZeRO-1 shard shapes {[s.data.shape for s in shards]} are not "
            f"1/{n_chips} of {m.shape}",
        )
    shard_shape = list(moments[0].addressable_shards[0].data.shape)
    in_use = [d.memory_stats() for d in devices]
    if all(s is not None for s in in_use):
        in_use = [s["bytes_in_use"] for s in in_use]
        check(all(b > 0 for b in in_use), f"idle device: bytes_in_use {in_use}")
    else:
        in_use = None  # CPU rehearsal: the backend reports no stats

    text = compiled_train_step_text(config, overrides, devices=devices)
    from distributeddeeplearning_tpu.utils.hlo import collective_counts

    counts = {k: v for k, v in collective_counts(text).items() if v}
    check(counts.get("all-reduce", 0) > 0, f"no all-reduce: {counts}")
    check(counts.get("all-gather", 0) > 0, f"no all-gather (ZeRO-1): {counts}")
    if expect_kernels:
        # The TPU pipeline keeps reduce-scatter as one op; the CPU emitter
        # lowers it as all-reduce + slice, so the rehearsal cannot ask.
        check(
            counts.get("reduce-scatter", 0) > 0,
            f"no reduce-scatter (ZeRO-1): {counts}",
        )
        check("tpu_custom_call" in text, "no tpu_custom_call under shard_map")
    del state, trainer

    losses_1, _, _ = _dp_run(config, overrides, jax.devices()[:1], steps)
    # Three steps on fresh batches: agreement is the check, not descent.
    check_losses(losses_n, steps=steps, first_loss=first_loss, must_fall=False)
    diffs = [abs(a - b) for a, b in zip(losses_n, losses_1)]
    check(
        max(diffs) <= loss_tol,
        f"{n_chips}-chip losses {losses_n} vs 1-chip {losses_1}: "
        f"differ by {max(diffs):.4f} > {loss_tol}",
    )
    return emit(
        f"train_gpt2_dp{n_chips}", t0, compiles.since(mark),
        steps=steps, losses=[round(x, 4) for x in losses_n],
        losses_one_chip=[round(x, 4) for x in losses_1],
        max_loss_diff=round(max(diffs), 5), loss_tol=loss_tol,
        zero1_shard_shape=shard_shape, zero1_full_shape=list(big.shape),
        bytes_in_use=in_use, collectives=counts, overrides=overrides,
    )


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    dev = device_record()
    if dev["platform"] != "tpu":
        sys.stderr.write(f"chip_smoke: needs a TPU, found {dev}\n")
        return 1
    if dev["count"] < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips}, found {dev}\n")
        return 1
    if not os.path.isdir(os.path.join(_REPO, "distributeddeeplearning_tpu")):
        sys.stderr.write("chip_smoke: the program is not beside this script\n")
        return 1
    sys.path.insert(0, _REPO)
    from distributeddeeplearning_tpu.utils.compat import setup_compile_cache

    cache_dir = setup_compile_cache()
    compiles = CompileLog()
    print(json.dumps({
        "phase": "start", "device": dev, "jax": jax.__version__,
        "compile_cache_dir": cache_dir, "seed": args.seed,
    }), flush=True)
    seed = [f"train.seed={args.seed}", f"data.seed={args.seed}"]
    ln_vocab = math.log(50257)

    if args.chips == 4:
        # Global batch 16: what one chip holds (7.9 GiB estimated by a
        # deviceless compile), so both sides of the comparison fit.
        phase_dp(
            GPT2_CONFIG, ["data.batch_size=16", *seed], steps=3, n_chips=4,
            loss_tol=DP_LOSS_TOL, first_loss=ln_vocab, expect_kernels=True,
            compiles=compiles,
        )
    else:
        # Only data.batch_size is cut, 32 -> 16 (7.9 GiB by a deviceless
        # compile of this step with the Mosaic kernels). The config's own 32
        # is estimated at 13.8 GiB and does run on the chip (4 steps of
        # `cli train`, PERF.md); 16 stays because the 20-step curve the
        # checks below expect was established at 16.
        # 20 steps: the synthetic stream is uniform random tokens, 8 distinct
        # batches — the loss sheds its initial excess over ln V, then falls
        # for good from step 8 on, when batches come round again.
        phase_train(
            "train_gpt2", GPT2_CONFIG, ["data.batch_size=16", *seed],
            steps=20, warmup_steps=0, first_loss=ln_vocab,
            expect_kernels=True, compiles=compiles,
        )
        # The conv control starts at exactly ln 1000 (zero-init head) on a
        # stream of random labels that never repeats, so nothing can fall:
        # one batch is repeated (n_distinct=1). Its lr of 0.4 diverges with
        # no warm-up at all (CPU rehearsal, batch 32: 6.9, 28, 31, 55), so
        # the 500-step warm-up is cut to 20, not to 0.
        phase_train(
            "train_resnet50", RESNET_CONFIG, ["data.n_distinct=1", *seed],
            steps=4, warmup_steps=20, first_loss=math.log(1000),
            expect_kernels=False, compiles=compiles,
        )
        phase_serve(
            GPT2_CONFIG,
            # attn_impl=xla is what `cli serve` asks of this config (flash
            # is the training kernel); the pool holds 58k tokens of KV.
            ["model.kwargs.attn_impl=xla", "data.batch_size=1",
             "serving.hbm_budget_mb=2048", *seed],
            prompt_lens=(7, 33, 120, 300), max_new_tokens=16,
            kernels=("gather", "pallas"), tol=SERVE_LOGIT_TOL,
            seed=args.seed, expect_kernels=True, compiles=compiles,
        )
        phase_kernels(
            heads=12, head_dim=64, seq=1024, pool_blocks=512, vocab=50257,
            embed=768,
            llama_kwargs=dict(size="300m", num_layers=2, vocab_size=32000,
                              max_len=1024),
            interpret=False, seed=args.seed, compiles=compiles,
        )
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
