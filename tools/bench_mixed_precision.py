"""Per-policy mixed-precision benchmark -> BENCH_MIXED_PRECISION.json.

One table, three rows — ``train.precision.policy`` in {fp32, bf16,
bf16_full} on the SAME workload (GPT-2 tiny, adamw, ZeRO-1, synthetic
tokens, dp=-1): the measured half of docs/MIXED_PRECISION.md's claims,
next to the HLO-level half pinned in tests/test_precision.py.

Each row is a real ``benchmark.run_benchmark`` run (no-recompilation
guard, per-step-synchronized p50/p90 latency window) and so carries:

- measured ``steps_per_sec`` + ``p50/p90_step_ms``. On this CPU-sim host
  the bf16 rows are NOT expected to be faster — XLA:CPU emulates bf16
  matmuls through f32 — so throughput here proves "no pathological
  regression", while the MXU win is a chip-run claim (not measured yet);
- measured per-member DURABLE state bytes (``parallel.fsdp
  .per_device_bytes`` over the real sharded init): fp32 keeps replicated
  fp32 params + sharded fp32 Adam moments; bf16 shards the fp32 masters
  (ZeRO-1) and re-gathers bf16 per step; bf16_full also stores moments
  in bf16 — the >= 3x param+opt-state reduction asserted by
  tests/test_precision.py and re-checked on this artifact;
- the analytic ring-model grad-sync wire bytes (``grad_sync_bytes`` with
  the policy's compute-dtype element width — the 2x the post-partitioner
  HLO dump proves structurally).

The ``modeled`` block generalizes the measurement: closed-form resident
state bytes/param/member under ZeRO-1 over N members (fp32: 4 + 8/N;
bf16: 12/N; bf16_full: 8/N) evaluated at the sim N and at a pod-scale
N=64, so the projection the acceptance bar names is explicit.

Usage: python tools/bench_mixed_precision.py  (writes the artifact at the
repo root, or $DDL_MP_OUT; $DDL_MP_STEPS overrides the timed window).
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# CPU simulation: platform and device count are read when jax is imported,
# so they are set before the first `import jax`.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

_OUT = os.environ.get(
    "DDL_MP_OUT", os.path.join(_REPO, "BENCH_MIXED_PRECISION.json")
)
_STEPS = int(os.environ.get("DDL_MP_STEPS", "20"))

POLICIES = ("fp32", "bf16", "bf16_full")


def _workload_cfg(policy: str):
    """GPT-2 tiny + adamw + ZeRO-1: the one shipped optimizer that supports
    every policy (sgd and adamw_fused fence bf16_full), over the sharding
    mode where the policy moves the most bytes (ZeRO-1 masters + gather).
    No ``model.kwargs.dtype``: the policy owns the compute dtype."""
    from distributeddeeplearning_tpu.config import (
        Config,
        DataConfig,
        ModelConfig,
        OptimConfig,
        PrecisionConfig,
        TrainConfig,
    )
    from distributeddeeplearning_tpu.mesh import MeshConfig

    return Config(
        model=ModelConfig(
            name="gpt2",
            kwargs={"size": "tiny", "max_len": 64, "vocab_size": 256},
        ),
        data=DataConfig(
            kind="synthetic_tokens", batch_size=16, seq_len=64,
            vocab_size=256, n_distinct=4,
        ),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(
            task="lm", log_every=0, zero1=True,
            precision=PrecisionConfig(policy=policy),
        ),
        mesh=MeshConfig(dp=-1),
    )


def _modeled_state_bytes_per_param(n: int) -> dict:
    """Closed-form per-member durable bytes/param under ZeRO-1 over ``n``
    members: params (replicated fp32 | sharded fp32 masters) + two Adam
    moments (sharded; fp32 | bf16)."""
    return {
        "fp32": round(4.0 + 8.0 / n, 4),
        "bf16": round((4.0 + 8.0) / n, 4),
        "bf16_full": round((4.0 + 4.0) / n, 4),
    }


def main() -> int:
    import jax

    from distributeddeeplearning_tpu.benchmark import run_benchmark

    n_dev = jax.device_count()
    policies = {}
    for policy in POLICIES:
        t0 = time.time()
        rec = run_benchmark(
            _workload_cfg(policy), warmup=3, steps=_STEPS,
            latency_steps=10, fused_probe=0,
        )
        policies[policy] = {
            "steps_per_sec": rec["steps_per_sec"],
            "p50_step_ms": rec["p50_step_ms"],
            "p90_step_ms": rec["p90_step_ms"],
            "loss": rec["loss"],
            "param_bytes_per_member": rec["param_bytes_per_member"],
            "opt_state_bytes_per_member": rec["opt_state_bytes_per_member"],
            "state_bytes_per_member": (
                rec["param_bytes_per_member"]
                + rec["opt_state_bytes_per_member"]
            ),
            "grad_sync_wire_bytes_analytic": rec["grad_sync_bytes_per_step"],
            "params": rec["params"],
            "bench_seconds": round(time.time() - t0, 1),
        }
        assert rec["precision"] == policy  # the knob reached the record
        print(f"{policy}: {policies[policy]['steps_per_sec']} steps/s, "
              f"state {policies[policy]['state_bytes_per_member']} B/member",
              flush=True)

    base = policies["fp32"]["state_bytes_per_member"]
    artifact = {
        "workload": "gpt2 tiny (vocab 256, seq 64) x adamw x zero1, "
                    "synthetic tokens, cpu-sim dp mesh",
        "platform_note": "CPU simulator: XLA:CPU emulates bf16 through f32, "
                         "so bf16 throughput parity (not speedup) is the "
                         "expectation here; the MXU speedup is not measured "
                         "yet. State and wire bytes are "
                         "platform-independent.",
        "sim_devices": n_dev,
        "timed_steps": _STEPS,
        "policies": policies,
        "state_bytes_reduction_vs_fp32": {
            p: round(base / policies[p]["state_bytes_per_member"], 2)
            for p in ("bf16", "bf16_full")
        },
        "grad_sync_reduction_vs_fp32": {
            p: round(
                policies["fp32"]["grad_sync_wire_bytes_analytic"]
                / policies[p]["grad_sync_wire_bytes_analytic"], 2
            )
            for p in ("bf16", "bf16_full")
        },
        "modeled": {
            "resident_state_bytes_per_param_per_member": {
                "formula": {"fp32": "4 + 8/N", "bf16": "12/N",
                            "bf16_full": "8/N"},
                f"at_n{n_dev}": _modeled_state_bytes_per_param(n_dev),
                "at_n64": _modeled_state_bytes_per_param(64),
            },
            "grad_sync_wire_bytes_per_member": {
                "formula": "2*(N-1)/N * params * elem_bytes "
                           "(ring all-reduce; elem 4B fp32 policy, "
                           "2B mixed policies)",
            },
        },
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    ratio = artifact["state_bytes_reduction_vs_fp32"]["bf16_full"]
    artifact["bf16_full_state_reduction_met"] = ratio >= 3.0

    tmp = _OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    os.replace(tmp, _OUT)
    print(f"wrote {_OUT} (bf16_full state reduction {ratio}x)")
    return 0 if artifact["bf16_full_state_reduction_met"] else 1


if __name__ == "__main__":
    sys.exit(main())
