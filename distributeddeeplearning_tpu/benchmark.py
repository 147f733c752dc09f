"""Benchmark harness — times a config's train step and reports throughput,
latency percentiles and (on a known TPU) MFU, always with the device named.

Methodology: synthetic (host-generated, deterministic) data so input IO never
gates the measurement; ``warmup`` steps to absorb compilation + autotuning;
then ``steps`` timed steps bounded by ``jax.block_until_ready`` on the final
state; throughput = items * steps / elapsed / device_count. A recompilation
inside the timed window would poison the number, so we assert the step cache
doesn't grow after warmup.
"""

from __future__ import annotations

import json
import pathlib
import time

import jax

from . import data as data_lib
from .config import Config
from .utils.pytree import tree_size

# bf16 peak TFLOP/s per chip, keyed by the exact ``device_kind`` jax reports.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
# A kind is added with its source when a chip of that kind has been run.
_PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def _peak_tflops(device) -> float | None:
    """The MFU denominator for ``device``. A TPU whose kind is not in the
    table is an error, not a default; on the CPU (tests) there is no peak
    and the record carries ``platform: cpu`` and no MFU."""
    if device.platform != "tpu":
        return None
    if device.device_kind not in _PEAK_TFLOPS:
        raise ValueError(
            f"no peak rate recorded for device_kind {device.device_kind!r}: "
            f"add it to benchmark._PEAK_TFLOPS with its source "
            f"(known: {sorted(_PEAK_TFLOPS)})"
        )
    return _PEAK_TFLOPS[device.device_kind]


def device_memory_stats() -> dict | None:
    """Peak/in-use device-memory bytes (max over local devices), or None on
    the CPU backend, whose ``memory_stats()`` is None."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return None
    return {
        "hbm_peak_bytes": max(s["peak_bytes_in_use"] for s in stats),
        "hbm_bytes_in_use": max(s["bytes_in_use"] for s in stats),
    }


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]




def run_benchmark(
    cfg: Config, *, warmup: int = 5, steps: int = 30,
    latency_steps: int | None = None, fused_probe: int | None = None,
) -> dict:
    """Time ``steps`` train steps of the config's workload. Returns the
    one-line JSON record the driver contract expects.

    Beyond mean steps/s, the record carries the host-dispatch picture:
    ``p50_step_ms``/``p90_step_ms`` from a per-step-synchronized window
    (``latency_steps`` steps, each bounded by ``block_until_ready`` — the
    full dispatch+compute+readback round trip), and — when ``fused_probe``
    (default: ``cfg.train.steps_per_call`` if > 1, else 8) allows —
    ``fused_steps_per_sec`` from a K-step fused-scan window plus
    ``dispatch_overhead_ms_per_step``, the unfused-minus-fused per-step
    delta: an estimate of what one host dispatch costs this config."""
    from .cli import build_all

    mesh, _, trainer, dataset = build_all(cfg)
    state = trainer.init(cfg.train.seed, dataset.batch(0))
    n_params = tree_size(state.params)

    # Device-resident input: a few DISTINCT batches are staged in HBM before
    # the timed window and cycled. The metric measures the training step, not
    # the synthetic generator. (Real-data input performance is the loader's
    # own benchmark, not this one.)
    n_staged = max(2, getattr(dataset, "n_distinct", 2))
    it = data_lib.sharded_batches(dataset.iter_from(0), mesh)
    staged = [next(it) for _ in range(n_staged)]
    jax.block_until_ready(staged)

    step = trainer.train_step
    for i in range(warmup):
        state, metrics = step(state, staged[i % n_staged])
    # Fence: a scalar readback of the last step's metrics on top of
    # block_until_ready forces the whole dependency chain.
    jax.block_until_ready(state)
    if warmup:
        float(jax.tree.leaves(metrics)[0])
    compiles_after_warmup = step._cache_size()

    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step(state, staged[i % n_staged])
    jax.block_until_ready(state)
    float(jax.tree.leaves(metrics)[0])
    elapsed = time.perf_counter() - t0

    # warmup=0 deliberately includes the compile in the window (functional
    # smoke use); with warmup, any in-window recompilation poisons the number.
    if warmup and step._cache_size() != compiles_after_warmup:
        raise RuntimeError(
            "train_step recompiled inside the timed window — benchmark invalid"
        )

    # items/step: images for vision tasks, tokens for LM/MLM tasks.
    b0 = dataset.batch(0)
    if "image" in b0:
        items, unit = b0["image"].shape[0], "images/sec/chip"
    else:
        key = "tokens" if "tokens" in b0 else "input_tokens"
        # Causal-LM batches carry seq_len+1 tokens; the model trains on L.
        length = b0[key].shape[1] - (1 if key == "tokens" else 0)
        items, unit = b0[key].shape[0] * length, "tokens/sec/chip"

    per_chip = items * steps / elapsed / jax.device_count()
    record = {
        "metric": f"{cfg.model.name}_{cfg.train.task}_throughput",
        "value": round(per_chip, 2),
        "unit": unit,
        "steps_per_sec": round(steps / elapsed, 4),
        "params": n_params,
        "device_count": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "loss": float(metrics["loss"]),
    }

    # Per-step latency distribution: each step individually fenced, so these
    # are full host-round-trip times (dispatch + compute + readback), unlike
    # the pipelined mean above — the spread between the two IS the dispatch
    # pipelining win. Nearest-rank p50/p90 over a short synchronized window.
    if latency_steps is None:
        latency_steps = min(steps, 12)
    if latency_steps:
        lats = []
        for i in range(latency_steps):
            t = time.perf_counter()
            state, _ = step(state, staged[i % n_staged])
            jax.block_until_ready(state)
            lats.append(time.perf_counter() - t)
        lats.sort()
        record["p50_step_ms"] = round(_percentile(lats, 0.5) * 1e3, 3)
        record["p90_step_ms"] = round(_percentile(lats, 0.9) * 1e3, 3)

    # Fused-dispatch probe: the same step body scanned K-per-call
    # (Trainer.fused_train_step). The unfused-vs-fused per-step delta
    # estimates host-dispatch overhead — the quantity steps_per_call exists
    # to amortize. Probe disabled with fused_probe<=1 or when the model's
    # step count is too small to time a call.
    if fused_probe is None:
        fused_probe = (
            cfg.train.steps_per_call if cfg.train.steps_per_call > 1 else 8
        )
    if fused_probe > 1:
        super_it = data_lib.sharded_superbatches(
            dataset.iter_from(0), mesh, fused_probe
        )
        staged_super = [next(super_it) for _ in range(2)]
        jax.block_until_ready(staged_super)
        fstep = trainer.fused_train_step(fused_probe)
        state, fmetrics = fstep(state, staged_super[0])  # compile + warm
        jax.block_until_ready(state)
        float(jax.tree.leaves(fmetrics)[0][-1])  # metrics are stacked [K]
        n_calls = max(2, steps // fused_probe)
        t0 = time.perf_counter()
        for i in range(n_calls):
            state, fmetrics = fstep(state, staged_super[i % 2])
        jax.block_until_ready(state)
        float(jax.tree.leaves(fmetrics)[0][-1])  # metrics are stacked [K]
        fused_elapsed = time.perf_counter() - t0
        fused_sps = n_calls * fused_probe / fused_elapsed
        record["steps_per_call_probe"] = fused_probe
        record["fused_steps_per_sec"] = round(fused_sps, 4)
        # Signed on purpose: a negative value means fusion LOST (e.g. the
        # scanned program spills) — that must be visible, not clamped away.
        record["dispatch_overhead_ms_per_step"] = round(
            (elapsed / steps - 1.0 / fused_sps) * 1e3, 3
        )
    # Gradient-sync wire bytes per member per step under the configured
    # grad_comm mode (analytic ring model, parallel/fsdp.grad_sync_bytes) —
    # the byte side of the compressed-collectives win (comms_quant.py): an
    # int8 row reads ~4x below the same config at fp32. 0 when dp == 1
    # (nothing to sync over).
    from .parallel.fsdp import grad_sync_bytes, per_device_bytes
    from .precision import get_policy

    policy = get_policy(cfg.train.precision.policy)
    record["grad_comm"] = cfg.train.grad_comm
    record["grad_sync_bytes_per_step"] = grad_sync_bytes(
        state.params,
        mode=cfg.train.grad_comm,
        block_size=cfg.train.grad_comm_block,
        n_members=mesh.shape["dp"],
        # Under a mixed policy the partitioner's all-reduce carries the
        # compute dtype — grads leave the backward pass in bf16.
        wire_elem_bytes=(
            policy.compute_dtype.itemsize if policy.mixed else None
        ),
    )
    # Overlap telemetry (docs/OVERLAP.md): when the bucketed/streamed sync
    # path is active, report the bucket partition's per-bucket wire bytes
    # (after padding and the grad_comm codec) and a rough per-step overlap
    # window: the backward time available for hiding all but the last
    # bucket's collective. The window is an ESTIMATE from p50 step time —
    # backward ~2/3 of a step, and the last of K buckets can't overlap
    # anything — not a measured collective schedule; bench_overlap.py
    # measures the realized fraction.
    record["update_sharding"] = cfg.train.update_sharding
    record["grad_bucket_mb"] = cfg.train.grad_bucket_mb
    # Multi-slice telemetry (comms_hier.py; docs/MULTISLICE.md): the
    # hierarchy knob as resolved plus the DCN-byte picture.
    # dcn_wire_bytes is the per-member payload that actually crosses
    # slices per step: under a flat sync on a hybrid mesh the ring spans
    # slices, so the FULL sync traffic rides DCN; under the hierarchical
    # path only the cross-slice all-reduce of the 1/ici shard does.
    from .comms_hier import HierTopology, phase_wire_bytes, resolve_hierarchy

    use_hier = resolve_hierarchy(cfg.train.comm_hierarchy, cfg.mesh.dcn_dp)
    record["comm_hierarchy"] = (
        "hierarchical" if use_hier else "flat"
    )
    record["dcn_dp"] = cfg.mesh.dcn_dp
    if (cfg.train.grad_bucket_mb > 0
            or cfg.train.update_sharding != "replicated" or use_hier):
        import flax.linen as nn

        from .comms_overlap import build_bucket_layout

        layout = build_bucket_layout(
            nn.meta.unbox(state.params),
            cfg.train.grad_bucket_mb,
            n_members=mesh.shape["dp"],
            block_size=cfg.train.grad_comm_block,
        )
        record["grad_buckets"] = layout.num_buckets
        record["grad_bucket_wire_bytes"] = layout.wire_bytes(
            cfg.train.grad_comm, cfg.train.grad_comm_block
        )
        if "p50_step_ms" in record:
            k = layout.num_buckets
            record["overlap_window_ms"] = round(
                record["p50_step_ms"] * (2.0 / 3.0) * (k - 1) / k, 3
            )
        if use_hier:
            topo = HierTopology(n=mesh.shape["dp"], dcn=cfg.mesh.dcn_dp)
            phases = phase_wire_bytes(
                sum(record["grad_bucket_wire_bytes"]), topo
            )
            record["hier_phase_wire_bytes"] = phases
            record["dcn_wire_bytes"] = phases["cross_all_reduce_bytes"]
    if not use_hier:
        record["dcn_wire_bytes"] = (
            record["grad_sync_bytes_per_step"] if cfg.mesh.dcn_dp > 1 else 0
        )
    # Mixed-precision telemetry (docs/MIXED_PRECISION.md): the policy plus
    # the measured per-member DURABLE state footprint it governs (local
    # shard bytes: replicated leaves count fully, ZeRO-1 shards 1/N).
    # Transient compute copies/activations show up only in hbm_peak_bytes.
    record["precision"] = policy.name
    record["param_bytes_per_member"] = per_device_bytes(state.params)
    record["opt_state_bytes_per_member"] = per_device_bytes(state.opt_state)
    # HBM telemetry: peak bytes decide e.g. whether a batch-512 cell even
    # fits. Key always present — null reads as "the CPU backend does not
    # report", never as "not recorded".
    mem = device_memory_stats()
    record["hbm_peak_bytes"] = mem["hbm_peak_bytes"] if mem else None
    if mem:
        record["hbm_bytes_in_use"] = mem["hbm_bytes_in_use"]
    # One AOT compile of the step, shared by the memory + FLOPs accounting
    # below: the AOT path does not reuse the traced-call executable (a real
    # second compile, or a persistent-cache hit), so compile it once.
    compiled = step.lower(state, staged[0]).compile()
    # The COMPILER's buffer accounting (argument/output/temp bytes), which
    # unlike the runtime stats above reports on every backend.
    from .telemetry import memory_analysis_dict

    record["memory_analysis"] = memory_analysis_dict(compiled)

    # MFU accounting (VERDICT.md next-round #2): per-device FLOPs of the
    # compiled step from XLA itself, achieved TFLOP/s over the timed window,
    # and utilization against the chip's bf16 peak when the kind is known.
    flops = float(compiled.cost_analysis().get("flops", 0.0))
    if flops > 0:
        achieved = flops * steps / elapsed / 1e12
        record["model_tflops_per_step"] = round(flops / 1e12, 4)
        record["achieved_tflops_per_sec"] = round(achieved, 3)
        peak = _peak_tflops(jax.devices()[0])
        if peak:
            record["mfu"] = round(achieved / peak, 4)
    return record


def vs_baseline(
    metric: str, value: float, repo_root: str | None = None, record: bool = False
) -> float | None:
    """Ratio vs the committed measurement in ``BENCH_BASELINE.json``
    (>1.0 = faster). No such file is committed today — the one on-chip record
    predated most of the code and was deleted — so every metric reports
    ``None`` until a chip run establishes one.

    Read-only unless ``record=True`` (used once, deliberately, to establish a
    baseline that is then reviewed and committed — a benchmark run must not
    dirty the checkout as a side effect). A metric with NO committed baseline
    reports ``None`` (JSON null), never 1.0: absence of a comparison must be
    visible, not flattered."""
    root = pathlib.Path(repo_root or pathlib.Path(__file__).resolve().parent.parent)
    path = root / "BENCH_BASELINE.json"
    table = {}
    if path.exists():
        table = json.loads(path.read_text())
    if metric not in table:
        if not record:
            return None
        table[metric] = value
        path.write_text(json.dumps(table, indent=2) + "\n")
    return round(value / table[metric], 4)
