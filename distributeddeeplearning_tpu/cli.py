"""CLI entry points: ``python -m distributeddeeplearning_tpu.cli <cmd>``.

Subcommands mirror the reference's per-config training entrypoints
(``BASELINE.json:5`` "the existing training entrypoints"): one config file per
workload.
"""

from __future__ import annotations

import argparse
import json
import sys

import flax.struct
import jax
import jax.numpy as jnp

from . import data as data_lib
from . import models
from .config import Config, apply_overrides, load_config
from .mesh import build_mesh, init_distributed
from .metrics import MetricWriter, Profiler
from .train import Trainer, fit, get_task, make_optimizer, parse_fault_injection
from .utils.pytree import tree_size


def build_all(cfg: Config, split: str = "train", devices=None,
              fault_nan_step: int | None = None):
    """Construct (mesh, model, trainer, dataset) from a config.

    ``split='eval'`` builds the dataset from the eval-split kwargs instead —
    used by ``cmd_eval`` so a standalone eval doesn't also load the training
    data (for record-file kinds that would hold the file in memory twice).
    ``devices`` overrides the mesh's device set — tools/aot_tpu_check.py
    passes ABSTRACT topology devices to AOT-compile the exact train step a
    real run of this config would execute. ``fault_nan_step`` compiles the
    ``nan:K`` gradient-poison fault into the train step (train.py)."""
    from .precision import check_precision_composition

    # Resolve + fence the mixed-precision policy BEFORE the model build so
    # an illegal policy/optimizer pair fails by name in milliseconds.
    policy = check_precision_composition(
        cfg.train.precision.policy, optim_name=cfg.optim.name
    )
    # Overlap/update-sharding x optimizer fences (comms_overlap.py): the
    # Trainer only sees an opaque optax transformation, so the per-name
    # optimizer checks (adamw_fused, weight_decay, grad_clip) live at the
    # config seam — before the model build, like the precision fence above.
    from .comms_overlap import check_update_sharding_config

    check_update_sharding_config(
        update_sharding=cfg.train.update_sharding,
        grad_bucket_mb=cfg.train.grad_bucket_mb,
        optim_name=cfg.optim.name,
        weight_decay=cfg.optim.weight_decay,
        grad_clip=cfg.optim.grad_clip,
    )
    # Hierarchy x topology fence (comms_hier.py): mode-name and dcn_dp
    # sanity here, before the mesh build; the dp-divisibility check runs in
    # the Trainer where the resolved dp extent is known.
    from .comms_hier import check_comm_hierarchy_config

    check_comm_hierarchy_config(
        comm_hierarchy=cfg.train.comm_hierarchy, dcn_dp=cfg.mesh.dcn_dp
    )
    mesh = build_mesh(cfg.mesh, devices=devices)
    model = models.get_model(cfg.model.name, **cfg.model.kwargs)
    # Mesh-aware models (ring/Ulysses attention, pipelined stacks) need the
    # live mesh; a config that asked for those features but got no mesh would
    # otherwise silently fall back or fail at first call.
    updates = {}
    if hasattr(model, "mesh") and model.mesh is None:
        updates["mesh"] = mesh
    if cfg.train.remat != "none":
        if not hasattr(model, "remat"):
            raise ValueError(
                f"model {cfg.model.name!r} does not support remat"
            )
        updates["remat"] = cfg.train.remat
    if policy.mixed:
        # The model's compute dtype is DERIVED from the policy — the two
        # knobs disagreeing would either waste the policy (model casts the
        # compute copy back up) or mislead the reader (dtype kwarg ignored).
        explicit = cfg.model.kwargs.get("dtype")
        if explicit is not None and jnp.dtype(explicit) != policy.compute_dtype:
            raise ValueError(
                f"model.kwargs.dtype={explicit!r} conflicts with "
                f"train.precision.policy={policy.name!r} (compute dtype "
                f"{policy.compute_dtype.name}): drop model.kwargs.dtype — "
                "the policy owns the compute dtype (docs/MIXED_PRECISION.md)"
            )
        if not hasattr(model, "dtype"):
            raise ValueError(
                f"model {cfg.model.name!r} has no dtype field, so "
                f"train.precision.policy={policy.name!r} cannot set its "
                "compute dtype — use precision policy 'fp32'"
            )
        updates["dtype"] = policy.compute_dtype
    if updates:
        model = model.clone(**updates)
    tx = make_optimizer(
        cfg.optim.name,
        cfg.optim.lr,
        momentum=cfg.optim.momentum,
        b1=cfg.optim.b1,
        b2=cfg.optim.b2,
        weight_decay=cfg.optim.weight_decay,
        warmup_steps=cfg.optim.warmup_steps,
        schedule=cfg.optim.schedule,
        total_steps=cfg.train.steps,
        grad_clip=cfg.optim.grad_clip,
        precision=policy,
    )
    trainer_kw = {}
    if cfg.train.sequence_parallel:
        # Megatron SP as a config knob (VERDICT r3 #3: reachable without
        # source edits): swap in the rules preset that shards activations'
        # seq dim over tp between blocks.
        from .parallel.tp import tp_rules

        trainer_kw["rules"] = tp_rules(sequence_parallel=True)
    trainer = Trainer(
        model,
        tx,
        # get_task drops knobs a task's factory doesn't declare.
        get_task(
            cfg.train.task,
            head_chunk=cfg.train.head_chunk,
            label_smoothing=cfg.train.label_smoothing,
        ),
        mesh,
        grad_accum=cfg.train.grad_accum,
        zero1=cfg.train.zero1,
        grad_comm=cfg.train.grad_comm,
        grad_comm_block=cfg.train.grad_comm_block,
        grad_bucket_mb=cfg.train.grad_bucket_mb,
        update_sharding=cfg.train.update_sharding,
        dcn_dp=cfg.mesh.dcn_dp,
        comm_hierarchy=cfg.train.comm_hierarchy,
        precision=policy,
        # Trainer gates on health.enabled itself; passing it unconditionally
        # keeps the TrainState schema (health field present/absent)
        # consistent across train/eval/generate for one config.
        health=cfg.health,
        fault_nan_step=fault_nan_step,
        **trainer_kw,
    )
    data_kwargs = (
        cfg.data.eval_dataset_kwargs() if split == "eval"
        else cfg.data.dataset_kwargs()
    )
    dataset = data_lib.make_dataset(cfg.data.kind, **data_kwargs)
    return mesh, model, trainer, dataset


def make_eval_fn(cfg: Config, mesh, dataset=None):
    """Callable returning a fresh iterable of sharded eval-split batches —
    what ``fit(eval_fn=...)`` and ``cmd_eval`` consume. ``dataset`` reuses an
    already-built eval dataset instead of constructing a second one."""
    import itertools

    # File-backed kind with no held-out file: config.eval_dataset_kwargs
    # prints a loud training-file warning when it builds the kwargs below
    # (ADVICE r2 #2) — no separate CLI-level warning needed.
    eval_ds = dataset if dataset is not None else data_lib.make_dataset(
        cfg.data.kind, **cfg.data.eval_dataset_kwargs()
    )

    def eval_batches():
        it = itertools.islice(eval_ds.iter_from(0), cfg.train.eval_batches)
        return data_lib.sharded_batches(it, mesh)

    return eval_batches


def _restore(cfg: Config, trainer, probe_batch, verb: str):
    """The latest checkpoint's state, or None where
    ``train.checkpoint_dir`` is unset or holds none."""
    if not cfg.train.checkpoint_dir:
        return None
    from .checkpoint import CheckpointManager

    ckpt = CheckpointManager(cfg.train.checkpoint_dir)
    try:
        if ckpt.latest_step() is None:
            return None
        trainer.setup(probe_batch)
        state, _ = ckpt.restore(trainer.abstract_state_with_shardings())
        print(f"{verb} checkpoint at step {int(state.step)}")
        return state
    finally:
        ckpt.close()


def _restore_or_init(cfg: Config, trainer, probe_batch, verb: str):
    """Latest checkpoint (when ``train.checkpoint_dir`` has one) or a fresh
    init — the shared preamble of every non-training subcommand."""
    state = _restore(cfg, trainer, probe_batch, verb)
    if state is not None:
        return state
    print(f"no checkpoint found — {verb} freshly initialized params")
    return trainer.init(cfg.train.seed, probe_batch)


def cmd_eval(cfg: Config) -> int:
    """Standalone evaluation: restore the latest checkpoint (or init fresh
    when none exists) and report mean eval metrics — top-1 ``eval_accuracy``
    for the vision tasks (``BASELINE.json:2`` "top-1 parity")."""
    from .train import evaluate

    mesh, _, trainer, eval_ds = build_all(cfg, split="eval")
    state = _restore_or_init(cfg, trainer, eval_ds.batch(0), "evaluating")
    metrics = evaluate(trainer, state, make_eval_fn(cfg, mesh, dataset=eval_ds)())
    metrics["step"] = int(state.step)
    print(json.dumps(metrics))
    return 0


def cmd_generate(cfg: Config, prompts: list[str], max_new_tokens: int,
                 temperature: float, seed: int, *, top_k: int = 0,
                 top_p: float = 0.0, bench: bool = False) -> int:
    """Sample text from the latest checkpoint (or fresh init) with the
    KV-cache decoder (``generate.py``). Assumes a BYTE tokenizer
    (``prepare_data --tokenizer byte``): prompts are encoded as UTF-8
    bytes, completions decoded back. Repeating ``--prompt`` batches UNEVEN
    prompts (left-padded, HF semantics); ``--bench`` times prefill and the
    per-token decode scan separately (>= 3 reps, medians, recompile guard)
    and reports decode-only generated-tokens/sec as the headline, with the
    prefill and blended end-to-end rates as separate fields."""
    import numpy as np

    from .generate import generate as run_generate
    from .generate import pad_prompts

    # Cheap argument validation BEFORE the expensive model build/restore.
    if temperature == 0.0 and (top_k or top_p):
        raise ValueError(
            "--top-k/--top-p only apply when sampling — set --temperature"
        )
    if any(not p for p in prompts):
        raise ValueError("prompt must be non-empty")
    if bench and max_new_tokens < 2:
        raise ValueError(
            "--bench needs --max-new-tokens >= 2 (at least one per-token "
            "decode step to time)"
        )
    mesh, model, trainer, dataset = build_all(cfg)
    if not hasattr(model, "decode"):
        raise ValueError(
            f"model {cfg.model.name!r} has no KV-cache decode support"
        )
    # Byte tokenizer ONLY: any other vocab would make the UTF-8 prompt
    # encoding and completion decoding silently meaningless (a BPE model's
    # ids are not bytes) — refuse rather than print garbage.
    vocab = getattr(model, "vocab_size", 0)
    if vocab != 256:
        raise ValueError(
            f"cli generate requires a byte-tokenizer model "
            f"(vocab_size=256, got {vocab}): prompts are encoded as UTF-8 "
            "bytes and completions decoded back (prepare_data "
            "--tokenizer byte). Use generate.generate() directly for "
            "other tokenizers."
        )
    state = _restore_or_init(cfg, trainer, dataset.batch(0), "generating from")

    encoded = [
        np.frombuffer(p.encode("utf-8"), np.uint8).astype(np.int32)
        for p in prompts
    ]
    tokens, lens = pad_prompts(encoded, pad_id=0)
    if tokens.shape[1] + max_new_tokens > getattr(model, "max_len", 1 << 30):
        raise ValueError(
            f"prompt ({tokens.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) exceeds model max_len {model.max_len}"
        )
    # Decoding runs the xla core on one program — drop kernel/mesh options.
    updates = {}
    if hasattr(model, "attn_impl"):
        updates["attn_impl"] = "xla"
    if hasattr(model, "mesh") and model.mesh is not None:
        updates["mesh"] = None
    if updates:
        model = model.clone(**updates)
    kw = dict(
        max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, rng=jax.random.PRNGKey(seed),
        prompt_lens=lens,
    )
    record: dict = {"step": int(state.step)}
    if bench:
        # Prefill and the per-token scan timed SEPARATELY (>=3 reps,
        # medians, recompile guard): the headline decode_tokens_per_sec
        # counts generated tokens over decode-loop time only — prefill is
        # one cheap batched matmul and blending it in overstated the rate
        # ~2x (VERDICT r4 Weak #2). Prefill/e2e rates are separate fields.
        from .generate import decode_bench

        out, bench_rec = decode_bench(model, state.params, tokens, **kw)
        record.update(bench_rec)
    else:
        out = jax.block_until_ready(
            run_generate(model, state.params, tokens, **kw)
        )
    P = tokens.shape[1]
    results = []
    for i, p in enumerate(prompts):
        new = np.asarray(out[i, P:])
        results.append({
            "prompt": p,
            "completion": bytes(int(t) for t in new).decode(
                "utf-8", errors="replace"
            ),
        })
    record["results"] = results
    print(json.dumps(record))
    return 0


@flax.struct.dataclass
class ServedState:
    """What a server keeps of a training state: the step it was saved at
    and the parameters, in the dtype the model's config states."""

    step: jax.Array
    params: object


def serving_model_and_state(cfg: Config, model, trainer, dataset):
    """What ``cmd_serve`` hands the engine, from ``build_all``'s outputs: the
    restored (or freshly initialized) parameters, and the model cloned onto
    the xla attention core on one program (the engine re-fences this;
    mirrors cmd_generate). No optimizer state is built for a server: a
    fresh start initializes the parameters alone (``Trainer.init_params``)
    and a restore keeps them alone. Shared with ``chip_smoke.py`` and the
    benchmark's serving driver, which serve token ids at the published
    vocabulary that ``cmd_serve``'s byte-tokenizer fence refuses."""
    probe = dataset.batch(0)
    restored = _restore(cfg, trainer, probe, "serving from")
    if restored is None:
        print("no checkpoint found — serving from freshly initialized params")
        state = ServedState(
            step=jnp.zeros((), jnp.int32),
            params=trainer.init_params(cfg.train.seed, probe),
        )
    else:
        state = ServedState(step=restored.step, params=restored.params)
        jax.tree.map(
            lambda x: x.delete(),
            (restored.opt_state, restored.grad_residual),
        )
    updates = {}
    if hasattr(model, "attn_impl"):
        updates["attn_impl"] = "xla"
    if hasattr(model, "mesh") and model.mesh is not None:
        updates["mesh"] = None
    if updates:
        model = model.clone(**updates)
    return model, state


def cmd_serve(cfg: Config, prompts: list[str], max_new_tokens: int,
              temperature: float, seed: int, *, top_k: int = 0,
              top_p: float = 0.0) -> int:
    """Serve a batch of prompts through the continuous-batching engine
    (``serving/``; docs/SERVING.md): paged KV cache, AOT prefill/decode,
    requests joining and leaving the decode batch mid-flight. Same byte
    tokenizer contract as ``generate``; the ``serving`` config section
    sizes the engine (``serving.speculation=ngram:K`` turns on greedy
    speculative decoding — the stats record then carries the accept-rate
    block). Emits one JSON record with completions, per-request latency
    metrics, engine stats, and the lifecycle event stream."""
    import numpy as np

    from .serving import Request, ServingEngine, check_serving_composition

    # Composition fences FIRST (fail by name before any build/restore).
    check_serving_composition(cfg)
    if temperature > 0 and getattr(cfg.serving, "speculation", "off") != "off":
        # The per-request half of the speculation fence would only fire
        # at ServingEngine.submit, after a build + checkpoint restore —
        # every cli serve request shares one --temperature, so fail now.
        raise NotImplementedError(
            "cli serve --temperature > 0 x serving.speculation: "
            "speculative serving is greedy-only — drop --temperature or "
            "set serving.speculation=off"
        )
    if any(not p for p in prompts):
        raise ValueError("prompt must be non-empty")
    if temperature == 0.0 and (top_k or top_p):
        raise ValueError(
            "--top-k/--top-p only apply when sampling — set --temperature"
        )
    mesh, model, trainer, dataset = build_all(cfg)
    vocab = getattr(model, "vocab_size", 0)
    if vocab != 256:
        raise ValueError(
            f"cli serve requires a byte-tokenizer model (vocab_size=256, "
            f"got {vocab}): prompts are encoded as UTF-8 bytes and "
            "completions decoded back (prepare_data --tokenizer byte). "
            "Use serving.ServingEngine directly for other tokenizers."
        )
    model, state = serving_model_and_state(cfg, model, trainer, dataset)
    from .telemetry import Telemetry, resolve_dir

    requests = [
        Request(
            prompt=list(p.encode("utf-8")), max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
        )
        for p in prompts
    ]
    tel_extra = {}
    if cfg.serving.replicas > 1:
        # Router tier (serving/router.py; docs/SERVING.md): N engine
        # replicas behind gauge-driven dispatch. Each replica stamps its
        # own telemetry bundle (process_index=i) into the shared dir —
        # the layout telemetry_aggregate.build_fleet merges — so the
        # single top-level Telemetry is NOT built on this path (its p0
        # stamp would collide with replica 0's).
        from .serving import ReplicaRouter

        tdir = resolve_dir(cfg) if cfg.telemetry.enabled else None
        router = ReplicaRouter(
            model, state.params, cfg.serving, seed=seed, telemetry_dir=tdir,
        )
        router.warmup()
        for req in requests:
            router.submit(req)
        finished = router.run()
        router.write_trace()
        stats, events = router.stats(), router.events
        if tdir:
            tel_extra["telemetry_dir"] = tdir
    else:
        tel = Telemetry.from_config(cfg)
        engine = ServingEngine(
            model, state.params, cfg.serving, seed=seed, telemetry=tel
        )
        engine.warmup()
        for req in requests:
            engine.submit(req)
        finished = engine.run()
        tel.write_trace()
        stats, events = engine.stats(), engine.events
        if tel.enabled:
            tel_extra["telemetry"] = tel.registry.to_dict()
            tel_extra["telemetry_dir"] = tel.dir
    results = []
    for st in finished:
        m = st.metrics()
        m["prompt"] = bytes(st.request.prompt).decode("utf-8", "replace")
        m["completion"] = bytes(
            t for t in st.generated if 0 <= t < 256
        ).decode("utf-8", errors="replace")
        results.append(m)
    record = {
        "step": int(state.step),
        "results": results,
        "stats": stats,
        "events": events,
        **tel_extra,
    }
    print(json.dumps(record))
    return 0


def _train_once(cfg: Config, fault, telemetry=None) -> int:
    """One training attempt: build, restore-or-init, fit. Raises
    ``train.Preempted`` / ``train.HealthRollback`` for ``cmd_train``'s outer
    policy loop — re-entry restores the latest durable checkpoint, which is
    the whole rollback mechanism (the data iterator cannot rewind, so
    rollback == resume). ``telemetry`` (a ``telemetry.Telemetry``) brackets
    the attempt: goodput ledger opened at the resume step / closed on every
    exit path, trace written at the attempt boundary."""
    from .telemetry import NULL_TELEMETRY

    tel = telemetry if telemetry is not None else NULL_TELEMETRY
    mesh, _, trainer, dataset = build_all(
        cfg,
        fault_nan_step=(
            fault.step if fault is not None and fault.kind == "nan" else None
        ),
    )
    print(f"devices: {jax.device_count()}  mesh: {dict(mesh.shape)}")

    ckpt = None
    start_index = 0
    state = None
    if cfg.train.checkpoint_dir:
        from .checkpoint import CheckpointManager

        ckpt = CheckpointManager(cfg.train.checkpoint_dir)
        if ckpt.latest_step() is not None:
            # Resume: no init materialization — restore straight into the
            # mesh placement computed by setup(). restore() falls back to
            # the newest EARLIER durable step when the latest is corrupt.
            trainer.setup(dataset.batch(0))
            state, data_state = ckpt.restore(
                trainer.abstract_state_with_shardings()
            )
            start_index = int(data_state.get("next_index", int(state.step)))
            print(f"resumed from step {int(state.step)}")
    if state is None:
        state = trainer.init(cfg.train.seed, dataset.batch(0))
    print(f"model: {cfg.model.name}  params: {tree_size(state.params):,}")

    # Fused dispatch (steps_per_call > 1) consumes stacked super-batches;
    # prefetch keeps `prefetch_size` placed (super-)batches in flight so
    # H2D overlaps the compiled call either way.
    raw = dataset.iter_from(start_index)
    placed = (
        data_lib.sharded_superbatches(raw, mesh, cfg.train.steps_per_call)
        if cfg.train.steps_per_call > 1
        else data_lib.sharded_batches(raw, mesh)
    )
    batches = data_lib.prefetch(placed, size=cfg.data.prefetch_size)
    writer = MetricWriter(cfg.train.log_dir)
    profiler = Profiler(cfg.train.profile_steps, cfg.train.log_dir)
    if tel.ledger is not None:
        # Open AT the resume step: the ledger re-reads its sidecar here, so
        # steps an earlier attempt already passed classify rollback_replay.
        tel.ledger.open(start_index)
    try:
        fit(
            trainer,
            state,
            batches,
            steps=cfg.train.steps,
            log_every=cfg.train.log_every,
            steps_per_call=cfg.train.steps_per_call,
            log_fn=lambda m: print(json.dumps(m)),
            writer=writer,
            profiler=profiler,
            ckpt=ckpt,
            save_every=cfg.train.save_every,
            fault=fault,
            eval_every=cfg.train.eval_every,
            eval_fn=make_eval_fn(cfg, mesh) if cfg.train.eval_every else None,
            health=cfg.health if cfg.health.enabled else None,
            telemetry=tel,
        )
    finally:
        # Always drain the async checkpoint queue — an abandoned in-flight
        # save would silently roll resume back by save_every steps.
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        writer.close()
        # Attempt boundary: ledger record appended, newest trace replaced —
        # on EVERY exit path (clean, Preempted, HealthRollback unwind).
        if tel.ledger is not None:
            tel.ledger.close()
        tel.write_trace()
    return 0


def cmd_train(cfg: Config) -> int:
    import os

    from .supervisor import ATTEMPT_ENV, EXIT_PREEMPTED
    from .train import HealthRollback, Preempted, check_fusion_cadences

    fault = parse_fault_injection(cfg.train.fault_injection)
    attempt = int(os.environ.get(ATTEMPT_ENV, "0") or 0)
    if fault is not None and attempt > 0:
        # Injected faults are ONE-SHOT: a supervised restart replays the same
        # run without re-firing (else step:K would crash-loop forever and
        # hang:K would re-stall every attempt). Attempt 0 injects; every
        # restart recovers.
        print(json.dumps({
            "event": "fault_disarmed",
            "attempt": attempt,
            "fault": f"{fault.kind}:{fault.step}",
        }))
        fault = None

    # Cadence fences BEFORE the (expensive) model build: a steps_per_call
    # that can't compose with the configured boundaries fails in
    # milliseconds, by name. fit() re-checks with the resume step.
    check_fusion_cadences(
        cfg.train.steps_per_call,
        steps=cfg.train.steps,
        log_every=cfg.train.log_every,
        eval_every=cfg.train.eval_every,
        save_every=cfg.train.save_every,
        fault=fault,
    )
    if cfg.train.debug_nans:
        jax.config.update("jax_debug_nans", True)
    if cfg.train.debug_checks:
        jax.config.update("jax_enable_checks", True)

    # One Telemetry bundle per process (NULL when disabled): the attempt
    # stamp is the supervisor's, so a restarted child's ledger records and
    # flight files are attributable; an in-process health rollback REUSES
    # the bundle (same attempt, next ledger run, device registry kept so
    # the re-entered fit doesn't re-lower the step).
    from .telemetry import Telemetry

    tel = Telemetry.from_config(cfg, attempt=attempt)

    rollbacks = 0
    while True:
        try:
            return _train_once(cfg, fault, tel)
        except Preempted as p:
            # fit already force-saved synchronously; the exit code tells the
            # supervisor "done, do not restart".
            print(json.dumps({
                "event": "preempted_exit", "step": p.step, "saved": p.saved,
            }))
            return EXIT_PREEMPTED
        except HealthRollback as rb:
            rollbacks += 1
            if rollbacks > cfg.health.max_rollbacks:
                print(json.dumps({
                    "event": "rollback_give_up",
                    "rollbacks": rollbacks - 1,
                    "max_rollbacks": cfg.health.max_rollbacks,
                    "step": rb.step,
                }), file=sys.stderr)
                return 1
            print(json.dumps({
                "event": "rollback_restart",
                "rollbacks": rollbacks,
                "step": rb.step,
                "consecutive": rb.consecutive,
            }))
            # The retry models a TRANSIENT fault (the dominant real-world
            # case: a flipped bit, one poisoned batch): replay from the last
            # durable save with injection disarmed. A deterministic re-fire
            # would make rollback a loop, not a recovery.
            fault = None


def cmd_supervise(args) -> int:
    """Run ``train`` under the restart supervisor (``supervisor.py``): the
    child is this same CLI with the same ``--config``/``--override`` flags;
    restart/backoff/hang knobs come from the config's ``supervisor`` section.
    The supervising process itself never touches the accelerator — it is a
    pure process babysitter, so it can outlive any child crash."""
    import os

    from .supervisor import supervise_command

    cfg = apply_overrides(load_config(args.config), args.override)
    cmd = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli",
        "train", "--config", args.config,
    ]
    for o in args.override:
        cmd += ["--override", o]
    if args.xla_perf_flags:
        cmd.append("--xla-perf-flags")
    clear = ()
    if cfg.supervisor.clear_cache_on_crash:
        from .utils.compat import compile_cache_dir

        clear = (compile_cache_dir(),)
    # Telemetry seam: children write their attempt ledgers/flight records
    # into the SAME dir (the overrides above carry telemetry.* through);
    # the supervisor adds backoff records, hang/crash flight dumps, and
    # the exit goodput_summary — without ever touching the accelerator
    # (telemetry.py is stdlib-only).
    goodput_path = flight_dir = None
    if cfg.telemetry.enabled:
        from .telemetry import resolve_dir, resolve_process_index, stamped

        flight_dir = resolve_dir(cfg)
        os.makedirs(flight_dir, exist_ok=True)
        # Stamped per process (same resolution the child's Telemetry uses),
        # so the supervisor's backoff records land in the SAME sidecar its
        # child appends attempt records to — and N supervisors sharing one
        # dir never interleave into each other's replay classification.
        goodput_path = os.path.join(
            flight_dir,
            stamped(cfg.telemetry.goodput_file, resolve_process_index()),
        )
    return supervise_command(
        cmd, cfg.supervisor, crash_clear_paths=clear,
        goodput_path=goodput_path, flight_dir=flight_dir,
    )


def cmd_report(tdir: str) -> int:
    """Summarize a telemetry dir (``cli report --dir ...``): the FLEET
    aggregation pass (``telemetry_aggregate.build_fleet``) over every
    process's artifacts — merged Perfetto trace (written to
    ``trace_merged.json``), pod goodput decomposition, straggler report,
    merged latency histograms/gauges, and the flight records present;
    the machine-readable form lands in ``<dir>/FLEET.json``. Accepts
    both the stamped fleet layout and pre-fleet single-process dirs.
    Pure stdlib — runs before ``init_distributed`` (no accelerator), so
    it works on a quarantined artifact dir copied off the pod."""
    from .telemetry_aggregate import build_fleet

    fleet = build_fleet(tdir)
    out: dict = {
        "dir": tdir,
        "goodput": fleet["goodput"],
        "trace": fleet["trace"] if fleet["trace"]["events"] else None,
        "flights": [f["file"] for f in fleet["flights"]],
        "straggler": fleet["straggler"],
        "histograms": fleet["histograms"],
        "gauges": fleet["gauges"],
        "processes": fleet["processes"],
        "headline": fleet["headline"],
        "fleet_json": "FLEET.json",
    }
    print(json.dumps(out, indent=2))
    return 0


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _fleet_plan(config: str, overrides: list[str], fleet: int, *,
                host: str = "127.0.0.1", port_base: int = 0,
                telemetry_dir: str | None = None,
                spill_dir: str | None = None,
                worker_args: list[str] | None = None,
                base_env: dict | None = None,
                roles: list[str] | None = None):
    """``[(cmd, env), ...]`` for every worker of ``cli serve --fleet N``
    — pure (no processes spawned), so tests can pin the plan.

    Workers are ``serving.worker`` invocations (one ServingEngine
    process each) reusing the ``cli launch`` child conventions: every
    child gets ``DDL_PROCESS_INDEX=i`` (the telemetry fleet stamp, so N
    workers sharing one telemetry dir write non-clobbering artifacts
    that ``telemetry_aggregate.build_fleet`` merges) and the coordinated
    -launch env vars are scrubbed — a fleet worker is single-process by
    construction.

    ``roles`` (from ``serving.prefill_replicas``) pins worker ``i`` to
    ``serving.role=roles[i]`` via a trailing override — trailing so it
    wins over any user-supplied role — and scrubs the fleet-level
    ``prefill_replicas`` knob (a child validates with ``fleet=1``, and
    the split topology is the PARENT'S concern; the child only needs
    its own phase). Because the plan is per-index, a supervisor respawn
    re-runs plan[i] and the worker rejoins with its original role."""
    import os

    plan = []
    for i in range(fleet):
        cmd = [
            sys.executable, "-m",
            "distributeddeeplearning_tpu.serving.worker",
            "--config", config,
            "--replica-index", str(i),
            "--host", host,
            "--port", str(port_base + i if port_base else 0),
        ]
        for o in overrides:
            cmd += ["--override", o]
        if roles is not None:
            cmd += ["--override", f"serving.role={roles[i]}",
                    "--override", "serving.prefill_replicas=0"]
        if telemetry_dir:
            cmd += ["--telemetry-dir", telemetry_dir]
        if spill_dir:
            # Per-worker KV spill checkpoint file: the fleet supervisor's
            # restart re-warms worker i from exactly the store worker i
            # checkpointed (indices are stable across restarts).
            cmd += ["--spill-store",
                    os.path.join(spill_dir, f"spill_w{i}.json")]
        if worker_args:
            cmd += list(worker_args)
        env = dict(os.environ if base_env is None else base_env)
        for k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
            env.pop(k, None)
        env["DDL_PROCESS_INDEX"] = str(i)
        plan.append((cmd, env))
    return plan


def read_worker_ready(stream, *, echo=None) -> dict:
    """Scan a worker's stdout for its single ``worker_ready`` JSON line
    (passing any other output through ``echo``); raises on EOF."""
    for line in iter(stream.readline, ""):
        try:
            rec = json.loads(line)
        except ValueError:
            rec = None
        if isinstance(rec, dict) and rec.get("event") == "worker_ready":
            return rec
        if echo is not None:
            echo(line)
    raise RuntimeError(
        "fleet worker exited before reporting worker_ready"
    )


def cmd_serve_fleet(args) -> int:
    """``cli serve --fleet N``: spawn N ``serving.worker`` processes
    (launch-style child machinery), dial their sockets, and serve the
    prompt batch through a ReplicaRouter whose replicas are
    SocketReplica transports — the cross-process counterpart of the
    in-process ``serving.replicas`` path, same dispatch/shed/drain/
    quarantine policy code. Like ``launch``, this runs BEFORE
    init_distributed: the parent is a process babysitter plus a socket
    client; the engines (and devices) belong to the children.

    The parent is not just a babysitter anymore: it runs a
    :class:`~.serving.fleet_supervisor.FleetSupervisor` control loop —
    a worker that exits, drops its socket, or goes heartbeat-silent is
    classified (supervisor.py taxonomy), its in-flight work retried on
    the survivors under a bumped attempt epoch, and the process itself
    restarted with exponential backoff (``serving.max_worker_restarts``
    / ``restart_backoff_*``), re-warming its KV spill tier from the
    ``--spill-store`` file it checkpointed (docs/FAULT_TOLERANCE.md)."""
    import os
    import subprocess
    import tempfile
    import threading

    from .config import apply_overrides, load_config
    from .serving import (
        Request,
        check_fleet_composition,
        check_serving_composition,
        connect_fleet,
    )
    from .serving.fleet_supervisor import FleetSupervisor
    from .serving.worker import ATTEMPT_ENV
    from .telemetry import resolve_dir

    cfg = apply_overrides(load_config(args.config), args.override)
    # Composition fences FIRST — fail by name before any child spawns.
    # fleet=args.fleet arms the self-healing fences (fault injection is
    # fleet-only; restart knobs must be sane).
    check_serving_composition(cfg, fleet=args.fleet)
    check_fleet_composition(cfg.serving, args.fleet)
    if (args.temperature > 0
            and getattr(cfg.serving, "speculation", "off") != "off"):
        raise NotImplementedError(
            "cli serve --temperature > 0 x serving.speculation: "
            "speculative serving is greedy-only — drop --temperature or "
            "set serving.speculation=off"
        )
    if any(not p for p in args.prompt):
        raise ValueError("prompt must be non-empty")
    tdir = resolve_dir(cfg) if cfg.telemetry.enabled else None
    # The KV re-warm chain needs a durable spill store per worker; only
    # meaningful when the spill tier exists at all.
    spill_dir = None
    if getattr(cfg.serving, "spill_blocks", 0) > 0:
        spill_dir = tdir or tempfile.mkdtemp(prefix="ddl_fleet_spill_")
    # Disaggregated topology: serving.prefill_replicas=K splits the
    # fleet into K prefill + (N-K) decode workers (fenced above: 0 < K
    # < fleet, prefix_cache on). Roles are pinned per plan index, so
    # supervisor respawns preserve the topology.
    pr = int(getattr(cfg.serving, "prefill_replicas", 0))
    roles = (
        ["prefill"] * pr + ["decode"] * (args.fleet - pr)
        if pr > 0 else None
    )
    plan = _fleet_plan(
        args.config, args.override, args.fleet,
        host=cfg.serving.worker_host,
        port_base=cfg.serving.worker_port,
        telemetry_dir=tdir,
        spill_dir=spill_dir,
        roles=roles,
    )
    procs = [None] * args.fleet
    threads, endpoints = [], []

    def _attach_stream(index, p):
        t = threading.Thread(
            target=_stream_prefixed,
            args=(p.stdout, f"[w{index}] ", sys.stdout),
            daemon=True,
        )
        t.start()
        threads.append(t)

    def _spawn_worker(index, attempt):
        """FleetSupervisor spawn hook: (re)launch worker ``index`` as
        restart ``attempt`` (stamped into $DDL_WORKER_ATTEMPT so one-shot
        fault injection never re-fires on the respawned process) and
        block until its ``worker_ready`` line."""
        cmd, env = plan[index]
        env = dict(env)
        env[ATTEMPT_ENV] = str(attempt)
        p = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        procs[index] = p
        ready = read_worker_ready(
            p.stdout,
            echo=lambda line: sys.stdout.write(f"[w{index}] {line}"),
        )
        _attach_stream(index, p)
        return p, ready

    try:
        # Initial boot stays parallel: spawn everyone, then collect the
        # ready lines (warmup compiles overlap across workers).
        for i, (cmd, env) in enumerate(plan):
            env = dict(env)
            env[ATTEMPT_ENV] = "0"
            procs[i] = subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        for i, p in enumerate(procs):
            ready = read_worker_ready(
                p.stdout,
                echo=lambda line, i=i: sys.stdout.write(f"[w{i}] {line}"),
            )
            endpoints.append((ready["host"], ready["port"]))
            _attach_stream(i, p)
        router = connect_fleet(cfg.serving, endpoints)
        supervisor = FleetSupervisor(
            router, procs, _spawn_worker, cfg.serving,
        )
        for p_text in args.prompt:
            router.submit(Request(
                prompt=list(p_text.encode("utf-8")),
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p,
            ))
        finished = supervisor.run()
        stats, events = router.stats(), router.events
        supervisor.shutdown()
    finally:
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.terminate()
    rcs = [p.wait() for p in procs if p is not None]
    for t in threads:
        t.join(timeout=5)
    results = []
    for st in finished:
        m = st.metrics()
        m["prompt"] = bytes(st.request.prompt).decode("utf-8", "replace")
        m["completion"] = bytes(
            t for t in st.generated if 0 <= t < 256
        ).decode("utf-8", errors="replace")
        results.append(m)
    record = {
        "fleet": args.fleet,
        "results": results,
        "stats": stats,
        "events": events,
        "supervisor": supervisor.stats(),
        "supervisor_events": supervisor.events,
        "worker_exit_codes": rcs,
    }
    if tdir:
        record["telemetry_dir"] = tdir
    print(json.dumps(record))
    return max(rcs) if rcs else 0


def _launch_plan(config: str, overrides: list[str], num_processes: int,
                 *, devices_per_process: int = 0, coordinator_port: int = 0,
                 xla_perf_flags: bool = False, base_env: dict | None = None,
                 independent: bool = False):
    """``[(cmd, env), ...]`` for every child of ``cli launch`` — pure
    (no processes spawned), so tests can pin the plan.

    Children are plain ``cli train`` invocations; the multiprocess runtime
    is threaded ENTIRELY through the env vars ``mesh.init_distributed``
    already consumes (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID), so
    a launched child and a manually started pod worker take the exact same
    code path. ``devices_per_process > 0`` additionally pins that many
    SIMULATED CPU devices per child (utils.compat.set_cpu_device_env) — the
    multiprocess CPU backend used for multi-slice rehearsal
    (docs/MULTISLICE.md); 0 leaves device discovery to the runtime (real
    TPU hosts).

    Every child gets ``DDL_PROCESS_INDEX`` — the telemetry layer's fleet
    stamp (``telemetry.resolve_process_index``), so N children sharing one
    ``--telemetry`` dir write non-clobbering per-process artifacts.
    ``independent=True`` skips the coordinator rendezvous entirely: the
    children run as N UNCOORDINATED single-process workers (each with its
    own device view). That is the fleet-observability rehearsal mode — the
    shared-telemetry-dir shape of a pod launch on a machine whose CPU
    backend cannot rendezvous (multiprocess CPU needs jax >= 0.5,
    docs/MULTISLICE.md) — and the N-replica serving shape of ROADMAP
    item 1."""
    import os

    if num_processes < 2:
        raise ValueError(
            f"--num-processes={num_processes}: a multiprocess launch needs "
            ">= 2 (single-process runs don't need the launcher)"
        )
    port = None if independent else (coordinator_port or _free_port())
    cmd = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli",
        "train", "--config", config,
    ]
    for o in overrides:
        cmd += ["--override", o]
    if xla_perf_flags:
        cmd.append("--xla-perf-flags")
    plan = []
    for pid in range(num_processes):
        env = dict(os.environ if base_env is None else base_env)
        if not independent:
            env["COORDINATOR_ADDRESS"] = f"localhost:{port}"
            env["NUM_PROCESSES"] = str(num_processes)
            env["PROCESS_ID"] = str(pid)
        else:
            # A previous coordinated run's env must not leak into the
            # children: they are single-process by construction.
            for k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
                env.pop(k, None)
        env["DDL_PROCESS_INDEX"] = str(pid)
        if devices_per_process > 0:
            from .utils.compat import set_cpu_device_env

            env["JAX_PLATFORMS"] = "cpu"
            set_cpu_device_env(env, devices_per_process)
        plan.append((list(cmd), env))
    return plan


def _stream_prefixed(stream, prefix: str, out) -> None:
    """Copy ``stream`` to ``out`` line-by-line with a ``[pK] `` prefix, so
    the interleaved stdout of N children (log lines AND JSON events) stays
    attributable to its process."""
    for line in iter(stream.readline, ""):
        out.write(prefix + line)
        out.flush()
    stream.close()


def cmd_launch(args) -> int:
    """Spawn ``--num-processes`` coordinated ``cli train`` workers on this
    machine (docs/MULTISLICE.md). The launcher itself never touches the
    accelerator — like ``supervise``, it runs BEFORE ``init_distributed``
    so the backend and coordinator port belong to the children. Exit code
    is the max over children (0 only when every worker succeeded)."""
    import subprocess
    import threading

    plan = _launch_plan(
        args.config, args.override, args.num_processes,
        devices_per_process=args.devices_per_process,
        coordinator_port=args.coordinator_port,
        xla_perf_flags=args.xla_perf_flags,
        independent=args.independent,
    )
    procs, threads = [], []
    for pid, (cmd, env) in enumerate(plan):
        p = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        t = threading.Thread(
            target=_stream_prefixed,
            args=(p.stdout, f"[p{pid}] ", sys.stdout),
            daemon=True,
        )
        t.start()
        procs.append(p)
        threads.append(t)
    rcs = [p.wait() for p in procs]
    for t in threads:
        t.join(timeout=5)
    for pid, rc in enumerate(rcs):
        if rc:
            print(f"[launch] process {pid} exited {rc}", file=sys.stderr)
    return max(rcs)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="distributeddeeplearning_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)
    for name in ("train", "eval", "generate", "serve", "supervise",
                 "launch"):
        p = sub.add_parser(name)
        p.add_argument("--config", required=True, help="path to a config .py")
        p.add_argument(
            "--override",
            action="append",
            default=[],
            metavar="a.b=v",
            help="dotted config override (repeatable)",
        )
        p.add_argument(
            "--xla-perf-flags",
            action="store_true",
            help="apply mesh.XLA_PERF_FLAGS (async-collective overlap) "
            "before backend init",
        )
        p.add_argument(
            "--telemetry",
            nargs="?",
            const="",
            default=None,
            metavar="DIR",
            help="enable unified telemetry (spans/goodput/flight recorder; "
            "docs/OBSERVABILITY.md) — sugar for telemetry.* overrides; "
            "optional DIR overrides the default quarantine-adjacent "
            "<checkpoint_dir>/telemetry output dir",
        )
        if name in ("generate", "serve"):
            p.add_argument(
                "--prompt", required=True, action="append",
                help="repeatable: a batch of (uneven) prompts decodes "
                "together (generate: left padding; serve: continuous "
                "batching over serving.slots lanes)",
            )
            p.add_argument("--max-new-tokens", type=int, default=64)
            p.add_argument("--temperature", type=float, default=0.0)
            p.add_argument("--top-k", type=int, default=0)
            p.add_argument("--top-p", type=float, default=0.0)
            p.add_argument("--seed", type=int, default=0)
        if name == "serve":
            p.add_argument(
                "--fleet", type=int, default=0,
                help="spawn N serving.worker child processes and route "
                "over sockets (cross-process fleet; docs/SERVING.md). "
                "0 = in-process serving.replicas path",
            )
        if name == "generate":
            p.add_argument(
                "--bench", action="store_true",
                help="re-run the compiled decode loop once and report "
                "steady-state tokens/sec",
            )
        if name == "launch":
            p.add_argument(
                "--num-processes", type=int, required=True,
                help="coordinated train workers to spawn (>= 2)",
            )
            p.add_argument(
                "--devices-per-process", type=int, default=0,
                help="pin this many SIMULATED CPU devices per worker "
                "(multiprocess CPU backend rehearsal); 0 = let the "
                "runtime discover real devices",
            )
            p.add_argument(
                "--coordinator-port", type=int, default=0,
                help="jax.distributed coordinator port (0 = pick a free "
                "one)",
            )
            p.add_argument(
                "--independent", action="store_true",
                help="skip the coordinator rendezvous: run the N workers "
                "as independent single-process jobs sharing one "
                "--telemetry dir (fleet-observability rehearsal; "
                "docs/OBSERVABILITY.md)",
            )
    pr = sub.add_parser("report")
    pr.add_argument(
        "--dir", required=True,
        help="telemetry output dir (the run's --telemetry DIR, or the "
        "default <checkpoint_dir>/telemetry)",
    )
    args = parser.parse_args(argv)
    if args.cmd == "report":
        # Pure artifact reader — no backend, no config, no rendezvous.
        return cmd_report(args.dir)
    # Before anything compiles; babysitter parents (supervise/launch/fleet)
    # compile nothing, their children come through here themselves.
    from .utils.compat import setup_compile_cache

    setup_compile_cache()
    if getattr(args, "telemetry", None) is not None:
        # Desugar BEFORE the supervise/launch dispatch: both build their
        # child command line from args.override, so children inherit the
        # exact same telemetry config as the parent resolved.
        args.override = list(args.override) + ["telemetry.enabled=True"]
        if args.telemetry:
            args.override.append(f"telemetry.dir={args.telemetry}")
    if args.cmd == "supervise":
        # BEFORE init_distributed: the supervisor must not claim the backend
        # or the coordinator port its children need.
        return cmd_supervise(args)
    if args.cmd == "launch":
        # Same reason: the launcher is a pure process babysitter — the
        # backend and coordinator rendezvous belong to its children.
        return cmd_launch(args)
    if args.cmd == "serve" and args.fleet:
        # Same reason again: the fleet parent is a babysitter plus a
        # socket client; the engines (and devices) live in the workers.
        return cmd_serve_fleet(args)
    if args.xla_perf_flags:
        # Env-level, so it must precede EVERY backend touch — including the
        # rendezvous below and anything a config module might do.
        from .mesh import apply_xla_perf_flags

        print(f"XLA_FLAGS: {apply_xla_perf_flags()}")
    # Multi-host rendezvous (no-op single-process); must precede any
    # backend/device use — in particular it runs BEFORE the config module
    # (an arbitrary .py) executes, so a config that calls
    # jax.device_count() sees the global device view.
    init_distributed()
    cfg = apply_overrides(load_config(args.config), args.override)
    if args.cmd == "train":
        return cmd_train(cfg)
    if args.cmd == "eval":
        return cmd_eval(cfg)
    if args.cmd == "generate":
        return cmd_generate(
            cfg, args.prompt, args.max_new_tokens, args.temperature,
            args.seed, top_k=args.top_k, top_p=args.top_p, bench=args.bench,
        )
    if args.cmd == "serve":
        return cmd_serve(
            cfg, args.prompt, args.max_new_tokens, args.temperature,
            args.seed, top_k=args.top_k, top_p=args.top_p,
        )
    return 2


if __name__ == "__main__":
    sys.exit(main())
