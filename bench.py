#!/usr/bin/env python
"""Benchmark entry point: ResNet-50 / synthetic-ImageNet images/sec/chip,
bf16 compute, batch 256, data-parallel over every attached chip. One process;
prints ONE JSON line that names the device it ran on.

A measurement path that finds no TPU fails: there is no CPU stand-in, because
a CPU timing under a device metric's name is worse than no number."""

from __future__ import annotations

import json
import sys


def main() -> int:
    import jax

    from distributeddeeplearning_tpu.utils.compat import setup_compile_cache

    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"bench.py: needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}) — not measuring\n"
        )
        return 1

    from distributeddeeplearning_tpu.benchmark import run_benchmark, vs_baseline
    from distributeddeeplearning_tpu.config import (
        Config,
        DataConfig,
        MeshConfig,
        ModelConfig,
        OptimConfig,
        TrainConfig,
    )

    cfg = Config(
        model=ModelConfig(
            name="resnet50", kwargs={"num_classes": 1000, "dtype": "bfloat16"}
        ),
        data=DataConfig(
            kind="synthetic_image", batch_size=256, image_size=224,
            num_classes=1000, n_distinct=4,
        ),
        optim=OptimConfig(name="sgd", lr=0.1, momentum=0.9),
        train=TrainConfig(task="classification", log_every=0),
        mesh=MeshConfig(dp=-1),
    )
    record = run_benchmark(cfg, warmup=5, steps=30)
    out = {"metric": "resnet50_imagenet_images_per_sec_per_chip"}
    for key in ("value", "unit", "platform", "device_kind", "device_count",
                "steps_per_sec", "model_tflops_per_step",
                "achieved_tflops_per_sec", "mfu", "p50_step_ms",
                "p90_step_ms", "steps_per_call_probe", "fused_steps_per_sec",
                "dispatch_overhead_ms_per_step", "hbm_peak_bytes"):
        if key in record:
            out[key] = record[key]
    # null until a chip run's record is committed as BENCH_BASELINE.json
    out["vs_baseline"] = vs_baseline(out["metric"], out["value"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
