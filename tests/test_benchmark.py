"""Benchmark harness as test (SURVEY §4 tier 6): the measurement machinery
itself is CI-checked — throughput is positive, the no-recompilation guard
holds, the record names its device, ``vs_baseline`` is null (never 1.0)
where no baseline is committed, and the peak-rate table is keyed by exact
``device_kind`` (an unknown TPU is an error, the CPU has no MFU).
"""

import json

import pytest

from distributeddeeplearning_tpu.benchmark import (
    _peak_tflops,
    run_benchmark,
    vs_baseline,
)
from distributeddeeplearning_tpu.config import (
    Config,
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    TrainConfig,
)


def _tiny_cfg():
    return Config(
        model=ModelConfig(name="resnet18", kwargs={"num_classes": 10}),
        data=DataConfig(
            kind="synthetic_image", batch_size=16, image_size=8,
            n_distinct=2,
        ),
        optim=OptimConfig(name="sgd", lr=0.1),
        train=TrainConfig(task="classification", log_every=0),
        mesh=MeshConfig(dp=-1),
    )


def test_run_benchmark_record_contract():
    record = run_benchmark(_tiny_cfg(), warmup=2, steps=3, fused_probe=0)
    assert record["value"] > 0
    assert record["steps_per_sec"] > 0
    assert record["unit"] == "images/sec/chip"
    assert record["device_count"] >= 1
    assert record["platform"] == "cpu"  # the pytest harness is CPU-pinned
    assert record["device_kind"] == "cpu"
    assert "mfu" not in record  # no peak rate on the CPU, so no utilisation
    assert record["params"] > 1e6
    # HBM telemetry key is ALWAYS present (VERDICT r4 Weak #5); the CPU
    # backend doesn't implement memory_stats, so here it must be null —
    # "backend does not report", distinguishable from "not recorded".
    assert "hbm_peak_bytes" in record
    assert record["hbm_peak_bytes"] is None
    # Per-step latency percentiles ride along by default (dispatch-overhead
    # telemetry): nearest-rank over a synchronized window, so p90 >= p50.
    assert record["p90_step_ms"] >= record["p50_step_ms"] > 0
    # Mixed-precision telemetry: policy name plus measured per-member
    # durable-state footprints (fp32 here — the default config).
    assert record["precision"] == "fp32"
    assert record["param_bytes_per_member"] > 0
    assert record["opt_state_bytes_per_member"] >= 0
    # The record must be JSON-serializable as-is (driver contract: one line).
    json.dumps(record)


def test_run_benchmark_zero_warmup_is_legal():
    record = run_benchmark(
        _tiny_cfg(), warmup=0, steps=2, latency_steps=0, fused_probe=0
    )
    assert record["value"] > 0
    # Both probe windows disabled -> none of their keys leak into the record.
    for key in ("p50_step_ms", "p90_step_ms", "steps_per_call_probe",
                "fused_steps_per_sec", "dispatch_overhead_ms_per_step"):
        assert key not in record


def test_run_benchmark_hierarchy_telemetry():
    # Multi-slice telemetry (docs/MULTISLICE.md): the resolved hierarchy,
    # dcn_dp, per-phase wire bytes, and dcn_wire_bytes — the cross-slice
    # all-reduce of the 1/ici shard is the ONLY DCN traffic under the
    # hierarchical path.
    from dataclasses import replace

    cfg = _tiny_cfg()
    cfg = replace(
        cfg,
        mesh=MeshConfig(dp=8, dcn_dp=2),
        train=replace(cfg.train, comm_hierarchy="auto"),
    )
    record = run_benchmark(cfg, warmup=0, steps=2, latency_steps=0,
                           fused_probe=0)
    assert record["comm_hierarchy"] == "hierarchical"
    assert record["dcn_dp"] == 2
    phases = record["hier_phase_wire_bytes"]
    total = sum(record["grad_bucket_wire_bytes"])
    ici = 4
    assert phases["intra_reduce_scatter_bytes"] == int(total * (ici - 1) / ici)
    assert phases["cross_all_reduce_bytes"] == int(total / ici * 2 * (2 - 1) / 2)
    assert record["dcn_wire_bytes"] == phases["cross_all_reduce_bytes"]
    # The hierarchy's whole point, in bytes: DCN traffic shrinks ~ici-fold
    # vs the flat ring on the same hybrid mesh.
    assert record["dcn_wire_bytes"] < record["grad_sync_bytes_per_step"] / 2
    json.dumps(record)


def test_run_benchmark_flat_dcn_telemetry():
    # Flat sync on a hybrid mesh: the ring spans slices, so the FULL sync
    # traffic rides DCN; on a single slice there is no DCN at all.
    from dataclasses import replace

    cfg = _tiny_cfg()
    flat_hybrid = replace(
        cfg,
        mesh=MeshConfig(dp=8, dcn_dp=2),
        train=replace(cfg.train, comm_hierarchy="flat"),
    )
    record = run_benchmark(flat_hybrid, warmup=0, steps=2, latency_steps=0,
                           fused_probe=0)
    assert record["comm_hierarchy"] == "flat"
    assert record["dcn_wire_bytes"] == record["grad_sync_bytes_per_step"] > 0
    assert "hier_phase_wire_bytes" not in record

    single = run_benchmark(_tiny_cfg(), warmup=0, steps=2, latency_steps=0,
                           fused_probe=0)
    assert single["dcn_dp"] == 1
    assert single["dcn_wire_bytes"] == 0


def test_run_benchmark_fused_probe_fields():
    # The fused-dispatch probe quantifies what steps_per_call amortizes:
    # an unfused-minus-fused per-step delta (signed — fusion may LOSE).
    record = run_benchmark(
        _tiny_cfg(), warmup=1, steps=4, latency_steps=2, fused_probe=2
    )
    assert record["steps_per_call_probe"] == 2
    assert record["fused_steps_per_sec"] > 0
    assert isinstance(record["dispatch_overhead_ms_per_step"], float)
    json.dumps(record)


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_vs_baseline_unknown_metric_is_null(tmp_path):
    # An absent baseline once reported 1.0, which read as "on par".
    assert vs_baseline("no_such_metric", 123.0, repo_root=str(tmp_path)) is None


def test_vs_baseline_known_metric_ratio(tmp_path):
    (tmp_path / "BENCH_BASELINE.json").write_text('{"m": 50.0}\n')
    assert vs_baseline("m", 100.0, repo_root=str(tmp_path)) == pytest.approx(2.0)


def test_vs_baseline_record_establishes_baseline(tmp_path):
    assert vs_baseline("m2", 40.0, repo_root=str(tmp_path), record=True) == 1.0
    table = json.loads((tmp_path / "BENCH_BASELINE.json").read_text())
    assert table["m2"] == 40.0
    # and is read back on the next call
    assert vs_baseline("m2", 80.0, repo_root=str(tmp_path)) == pytest.approx(2.0)


def test_peak_rate_is_keyed_by_exact_device_kind():
    assert _peak_tflops(_Dev("tpu", "TPU v5 lite")) == 197.0


def test_unknown_tpu_kind_is_an_error_not_a_default():
    # A substring match would have priced this as a v5e.
    with pytest.raises(ValueError, match="no peak rate recorded"):
        _peak_tflops(_Dev("tpu", "TPU v5 lite pod"))


def test_cpu_has_no_peak_rate():
    assert _peak_tflops(_Dev("cpu", "cpu")) is None
