"""Driver ``train_fit``: a training cell, timed through ``train.fit`` on the
trainer and mesh that ``cli.build_all`` returns, as ``cli._train_once``
drives them (input pipeline running, metrics deferred, ``log_every`` as the
config has it).

Set-up builds ONE trainer and state, swaps the seed's reference weights in,
drives the first steps through the window's own ``fit`` and feed (rows all
differ), reads off what ``correct`` compares, warms up, and hands the same
objects to the window: one ``fit`` call of a fixed number of steps that ends
in ``block_until_ready``. After the window the program's state is freed and
the plain reference follows the first steps (``harness/correct.py``).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmarks.harness import correct, trace as trace_lib
from benchmarks.harness.common import (
    REPO,
    BenchError,
    Cell,
    CompileLog,
    load_by_path,
    Phases,
    memory_peak_bytes,
    program_overrides,
    span_telemetry,
    spans_of,
    swap_in_reference_weights,
)

CHECK_STEPS = 3


class SeededBatches:
    """The traffic of a training cell: batch ``i`` is a pure function of
    (seed, i), made by the configuration's reference (``ref.batch``: tokens
    for a language model, every row different). Has the program's dataset
    interface (``batch``, ``iter_from``)."""

    def __init__(self, ref, seed: int, batch_size: int, traffic: dict, d: dict):
        self.ref, self.seed, self.batch_size = ref, int(seed), int(batch_size)
        self.traffic, self.d = traffic, d

    def batch(self, index: int) -> dict:
        return self.ref.batch(
            self.seed, int(index), self.batch_size, self.traffic, self.d
        )

    def iter_from(self, start: int = 0):
        i = start
        while True:
            yield self.batch(i)
            i += 1


def program_config(cell: Cell, seed: int):
    """The program's Config for this cell: its own config file, with the
    sizes of the configuration file and the traffic's batch over it."""
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    c, t = cell.config, cell.traffic
    prog, o = c["program"], c["optimizer"]
    global_batch = int(t["batch_per_chip"]) * cell.chips
    overrides = [
        f"data.batch_size={global_batch}",
        f"optim.lr={o['lr']}", f"optim.b1={o['b1']}", f"optim.b2={o['b2']}",
        f"optim.weight_decay={o['weight_decay']}",
        f"optim.warmup_steps={o['warmup_steps']}",
        f"optim.schedule={o['schedule']}",
        f"optim.grad_clip={o['grad_clip']}",
        f"train.steps={o['total_steps']}",
        f"train.seed={seed % (1 << 31)}",
        *program_overrides(cell),
    ]
    return apply_overrides(
        load_config(os.path.join(REPO, prog["config"])), overrides
    )


def _first_moment(opt_state):
    """The optimizer's first moments (FusedAdamWState.mu, or optax's
    ScaleByAdamState.mu inside a chain)."""
    import jax

    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")
    )
    for n in nodes:
        if hasattr(n, "mu"):
            return n.mu
    raise BenchError("no first moments (.mu) in the optimizer state")


def _norm_tree():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t
    ))


def _quiet(_m):
    pass


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, hooks: dict | None = None) -> dict:
    """Returns what ``run.py`` prints: checks, counts, the run record that
    the per-layer readers read, and the end-to-end metrics."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu import data as data_lib
    from distributeddeeplearning_tpu.train import fit

    hooks = hooks or {}
    ph = Phases(t_process)
    ph("imports")
    ref = load_by_path("references", cell.config["reference"])
    d = ref.dims(cell.config)
    t, o = cell.traffic, cell.config["optimizer"]
    compiles = CompileLog()
    cfg = program_config(cell, seed)
    global_batch = cfg.data.batch_size

    # -- build, as cli._train_once does ---------------------------------
    mesh, _model, trainer, _ = cli.build_all(cfg)
    ph("build_all")
    if "after_build" in hooks:
        hooks["after_build"](trainer)
    dataset = SeededBatches(ref, seed, global_batch, t, d)
    state = trainer.init(cfg.train.seed, dataset.batch(0))
    jax.block_until_ready(state)
    ph("trainer.init")

    state = swap_in_reference_weights(state, ref, d, seed)
    jax.block_until_ready(state.params)
    ph("weights")

    # -- the feed: the program's own pipeline over the seeded traffic ----
    recorded: list[dict] = []

    def feed():
        for b in dataset.iter_from(0):
            if len(recorded) < CHECK_STEPS:
                recorded.append({k: np.array(v) for k, v in b.items()})
            yield b

    batches = data_lib.prefetch(
        data_lib.sharded_batches(feed(), mesh), size=cfg.data.prefetch_size
    )

    def drive(state, until, *, log_every, telemetry=None):
        return fit(
            trainer, state, batches, steps=until, log_every=log_every,
            steps_per_call=cfg.train.steps_per_call, log_fn=_quiet,
            telemetry=telemetry,
        )

    # -- the first steps, through the window's own call and feed --------
    norms = _norm_tree()
    named = lambda tree: {  # noqa: E731
        k: float(v) for k, v in
        ref.leaf_names_of_program_tree(jax.device_get(tree)).items()
    }
    prog = {"losses": []}
    state, hist = drive(state, 1, log_every=1)
    ph("step 1")
    prog["losses"] += [float(m["loss"]) for m in hist if "loss" in m]
    scale = 1.0 / (1.0 - float(o["b1"]))
    prog["grad_norms"] = {
        k: v * scale for k, v in
        named(norms(_first_moment(state.opt_state))).items()
    }
    state, hist = drive(state, CHECK_STEPS, log_every=1)
    prog["losses"] += [float(m["loss"]) for m in hist if "loss" in m]
    delta_norms = jax.jit(lambda p, key: jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
        ref.program_tree(ref.weights_from_key(key, d), d),
    ))
    prog["delta_norms"] = named(delta_norms(state.params, ref.seed_key(seed)))
    ph("steps 2-3 and norms")

    # -- warm up at the window's cadence, and size the window -----------
    log_every = cfg.train.log_every
    done = CHECK_STEPS + int(t["warmup_steps"])
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state, _ = drive(state, done, log_every=log_every)
    jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / int(t["warmup_steps"])
    ph("warm-up")
    n_steps = max(int(t["warmup_steps"]), int(round(seconds / step_s)))
    trace_steps = min(int(t.get("trace_steps", 6)), n_steps // 2) if trace else 0

    tel = span_telemetry() if trace else None
    compile_setup_s = compiles.total()
    mark = compiles.mark()

    # -- the window: one fit call, ended by block_until_ready -----------
    timed_steps = n_steps - trace_steps
    t_w0 = time.perf_counter()
    state, hist = drive(
        state, done + timed_steps, log_every=log_every, telemetry=tel
    )
    jax.block_until_ready(state)
    t_w1 = time.perf_counter()
    window_s = t_w1 - t_w0
    losses = [float(m["loss"]) for m in hist if "loss" in m]

    record = {
        "cell": cell, "dims": d, "device_kind": jax.devices()[0].device_kind,
        "window_s": window_s, "steps": timed_steps,
        "samples": timed_steps * global_batch, "chips": cell.chips,
        "compile_s": compile_setup_s, "reference": ref,
        "step_s_warmup": step_s,
        "spans": [], "trace": None,
    }
    if trace:
        record["spans"] = spans_of(tel)
        tw = trace_lib.TraceWindow(os.path.join(REPO, ".bench_out", "trace"))
        tw.start()
        state, _ = drive(
            state, done + n_steps, log_every=log_every, telemetry=tel
        )
        jax.block_until_ready(state)
        tw.stop()
        record["trace"] = tw.reduce(
            spans_of(tel), keep=bool(hooks.get("keep_trace"))
        )
        record["trace_steps"] = trace_steps
    window_compiles = compiles.since(mark)
    compiles.close()
    ph.report(compiles)
    if window_compiles:
        raise BenchError(f"compiled inside the window: {window_compiles}")

    peak = memory_peak_bytes()
    failed = sum(1 for x in losses if not np.isfinite(x))

    # -- free the program's state, then the reference -------------------
    jax.tree.map(
        lambda x: x.delete() if hasattr(x, "delete") else None, state
    )
    del state, trainer, batches
    jax.clear_caches()
    t_ref = time.perf_counter()
    rows = int(cell.config.get("reference_rows_per_block", 2))
    want = ref.train_steps(seed, recorded, d, o, rows_per_block=rows)
    record["reference_s"] = time.perf_counter() - t_ref
    if hooks.get("in_place") == "control":
        # harness/faults.py: the reference at the precision below the
        # stated one, in the program's place
        prog = ref.train_steps(
            seed, recorded, d, o, rows_per_block=rows,
            precision=cell.config["control_precision"],
        )
    elif hooks.get("in_place") == "half_batch":
        prog = ref.train_steps(
            seed, recorded, d, o, rows_per_block=rows, half_batch=True
        )
    checks = correct.training_checks(prog, want, cell.config["limits"])
    sys.stderr.write(
        f"reference {record['reference_s']:.1f} s; whole run "
        f"{time.perf_counter() - t_process:.1f} s\n"
    )
    extra = {"losses": {"program": prog["losses"], "reference": want["losses"]}}

    metrics = {
        "setup_s": t_w0 - t_process,
        "train_samples_per_s": record["samples"] / window_s / cell.chips,
    }
    return {
        "checks": checks, "attempted": timed_steps, "failed": failed,
        "end_to_end": metrics, "record": record, "memory_peak_bytes": peak,
        "extra": extra,
    }
