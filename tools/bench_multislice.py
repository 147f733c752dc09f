"""Multi-slice gradient-collective benchmark -> BENCH_MULTISLICE.json.

One grid over the hierarchical-sync knobs (``comms_hier``, docs/
MULTISLICE.md) on the SAME workload (GPT-2 tiny, adamw, synthetic
tokens, bucketed sync, dp=8):

    comm_hierarchy x wire mode x dcn_dp
    (flat|hier)      (fp32|bf16|int8)  (2|4)

Every row is a real ``benchmark.run_benchmark`` run on the 8-device CPU
sim with a hybrid mesh of ``dcn_dp`` simulated slices: measured
``steps_per_sec`` + ``p50/p90_step_ms`` plus the multi-slice telemetry
benchmark.py records — the resolved hierarchy, per-phase wire bytes and
``dcn_wire_bytes`` (the bytes that would ride DCN on real hardware).

The artifact's point is the flat-vs-hierarchical comparison per cell:

  - ``dcn_byte_reduction``: flat_dcn_bytes / hier_dcn_bytes — the
    measured ~ici-fold shrink of cross-slice traffic, the number the
    whole subsystem exists for. This is telemetry-measured (from the
    compiled step's bucket layout), so it is real on the CPU sim too.
  - ``steps_per_sec_ratio``: hier / flat throughput. On this CPU sim
    ICI and DCN are the same memcpy, so the ratio is ~1 by construction
    and says nothing about DCN — the artifact states that.

``dcn_calibration`` distills the canonical fp32/dcn2 cell for
``tools/project_scaling.py``: when the flat-vs-hier step-time delta
clears the noise floor (a real multi-slice run), the measured effective
DCN byte rate ``(flat_dcn_bytes - hier_dcn_bytes) / delta_t`` replaces
the assumed ``DDL_DCN_GBPS``; on the CPU sim the delta is noise and the
field is null WITH the reason — never a fabricated constant.

A failed grid never clobbers a committed artifact: the file is written
atomically only after every row succeeded.

Usage: python tools/bench_multislice.py   (writes BENCH_MULTISLICE.json
at the repo root, or $DDL_MULTISLICE_OUT; $DDL_MULTISLICE_STEPS sets
the timed window, $DDL_MULTISLICE_MODES / $DDL_MULTISLICE_DCN the grid
axes, $DDL_MULTISLICE_BUCKET_MB the bucket size;
DDL_MULTISLICE_SHRINK=1 is the CI dry-run: fp32 only, dcn_dp=2, short
window).
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

_SHRINK = os.environ.get("DDL_MULTISLICE_SHRINK") == "1"
_OUT = os.environ.get(
    "DDL_MULTISLICE_OUT", os.path.join(_REPO, "BENCH_MULTISLICE.json")
)
_STEPS = int(os.environ.get(
    "DDL_MULTISLICE_STEPS", "4" if _SHRINK else "16"
))
_MODES = tuple(os.environ.get(
    "DDL_MULTISLICE_MODES", "fp32" if _SHRINK else "fp32,bf16,int8"
).split(","))
_DCN = tuple(int(d) for d in os.environ.get(
    "DDL_MULTISLICE_DCN", "2" if _SHRINK else "2,4"
).split(","))
_BUCKET_MB = float(os.environ.get("DDL_MULTISLICE_BUCKET_MB", "0.05"))
_DP = 8
# Flat-vs-hier p50 deltas below this fraction of the flat p50 are timing
# noise, not a DCN measurement.
_NOISE_FLOOR = 0.05


def _workload_cfg(*, mode: str, hierarchy: str, dcn_dp: int):
    from distributeddeeplearning_tpu.config import (
        Config,
        DataConfig,
        ModelConfig,
        OptimConfig,
        TrainConfig,
    )
    from distributeddeeplearning_tpu.mesh import MeshConfig

    return Config(
        model=ModelConfig(
            name="gpt2",
            kwargs={"size": "tiny", "max_len": 64, "vocab_size": 256,
                    "dropout_rate": 0.0},
        ),
        data=DataConfig(
            kind="synthetic_tokens", batch_size=16, seq_len=64,
            vocab_size=256, n_distinct=4,
        ),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(
            task="lm", log_every=0, grad_comm=mode,
            grad_bucket_mb=_BUCKET_MB, comm_hierarchy=hierarchy,
        ),
        mesh=MeshConfig(dp=_DP, dcn_dp=dcn_dp),
    )


def _run_grid() -> dict:
    from distributeddeeplearning_tpu.benchmark import run_benchmark

    rows: dict = {}
    for mode in _MODES:
        for dcn in _DCN:
            for hierarchy in ("flat", "hierarchical"):
                label = f"{mode}/dcn{dcn}/{hierarchy}"
                t0 = time.time()
                rec = run_benchmark(
                    _workload_cfg(mode=mode, hierarchy=hierarchy,
                                  dcn_dp=dcn),
                    warmup=1 if _SHRINK else 3, steps=_STEPS,
                    latency_steps=4 if _SHRINK else 10, fused_probe=0,
                )
                row = {
                    "steps_per_sec": rec["steps_per_sec"],
                    "p50_step_ms": rec["p50_step_ms"],
                    "p90_step_ms": rec["p90_step_ms"],
                    "loss": rec["loss"],
                    "grad_comm": rec["grad_comm"],
                    "comm_hierarchy": rec["comm_hierarchy"],
                    "dcn_dp": rec["dcn_dp"],
                    "dcn_wire_bytes": rec["dcn_wire_bytes"],
                    "grad_sync_bytes_per_step":
                        rec["grad_sync_bytes_per_step"],
                    "bench_seconds": round(time.time() - t0, 1),
                }
                for k in ("grad_buckets", "grad_bucket_wire_bytes",
                          "hier_phase_wire_bytes"):
                    if k in rec:
                        row[k] = rec[k]
                rows[label] = row
                print(f"{label}: {row['steps_per_sec']} steps/s "
                      f"dcn_wire={row['dcn_wire_bytes']}B", flush=True)
    return rows


def _comparisons(rows: dict) -> dict:
    out: dict = {}
    for mode in _MODES:
        for dcn in _DCN:
            flat = rows[f"{mode}/dcn{dcn}/flat"]
            hier = rows[f"{mode}/dcn{dcn}/hierarchical"]
            cell: dict = {
                "dcn_wire_bytes_flat": flat["dcn_wire_bytes"],
                "dcn_wire_bytes_hier": hier["dcn_wire_bytes"],
                "steps_per_sec_ratio": round(
                    hier["steps_per_sec"] / flat["steps_per_sec"], 4
                ),
            }
            if hier["dcn_wire_bytes"]:
                cell["dcn_byte_reduction"] = round(
                    flat["dcn_wire_bytes"] / hier["dcn_wire_bytes"], 2
                )
            out[f"{mode}/dcn{dcn}"] = cell
    return out


def _calibration(rows: dict) -> dict:
    """The canonical fp32/dcn2 cell as project_scaling.py inputs."""
    mode, dcn = _MODES[0], _DCN[0]
    flat = rows[f"{mode}/dcn{dcn}/flat"]
    hier = rows[f"{mode}/dcn{dcn}/hierarchical"]
    delta_ms = flat["p50_step_ms"] - hier["p50_step_ms"]
    delta_bytes = flat["dcn_wire_bytes"] - hier["dcn_wire_bytes"]
    cal = {
        "cell": f"{mode}/dcn{dcn}",
        "ici_size": _DP // dcn,
        "flat_p50_step_ms": flat["p50_step_ms"],
        "hier_p50_step_ms": hier["p50_step_ms"],
        "delta_ms": round(delta_ms, 4),
        "dcn_wire_bytes_flat": flat["dcn_wire_bytes"],
        "dcn_wire_bytes_hier": hier["dcn_wire_bytes"],
    }
    if delta_ms > _NOISE_FLOOR * flat["p50_step_ms"] and delta_bytes > 0:
        cal["effective_dcn_bytes_per_sec"] = round(
            delta_bytes / (delta_ms * 1e-3), 1
        )
    else:
        cal["effective_dcn_bytes_per_sec"] = None
        cal["reason"] = (
            "flat-vs-hier step-time delta within timing noise — on the "
            "CPU sim ICI and DCN are the same host memory, so the byte "
            "shrink cannot show up as time; re-run on a real multi-slice "
            "pod to measure the effective DCN rate"
        )
    return cal


def main() -> int:
    import jax

    try:
        rows = _run_grid()
    except Exception as e:
        # Refuse to clobber a committed artifact with a failed run: the
        # partial grid is printed for debugging but never written.
        print(f"grid FAILED ({type(e).__name__}: {e}); "
              f"leaving {_OUT} untouched", file=sys.stderr)
        raise

    artifact = {
        "workload": "gpt2 tiny (vocab 256, seq 64) x adamw, synthetic "
                    "tokens, bucketed sync, cpu-sim dp=8 hybrid mesh",
        "platform_note": "CPU simulator: every simulated slice lives in "
                         "one process, so ICI and DCN have identical "
                         "cost and steps_per_sec_ratio ~1 says nothing "
                         "about real DCN. The wire-byte telemetry (the "
                         "dcn_byte_reduction column) is exact — it comes "
                         "from the compiled step's bucket layout, the "
                         "same bytes tests/test_hier.py pins in HLO. "
                         "Re-run on a multi-slice pod for real timings; "
                         "project_scaling.py reads whatever calibration "
                         "is committed here.",
        "sim_devices": jax.device_count(),
        "dp": _DP,
        "timed_steps": _STEPS,
        "bucket_mb": _BUCKET_MB,
        "shrunk": _SHRINK,
        "rows": rows,
        "comparisons": _comparisons(rows),
        "dcn_calibration": _calibration(rows),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    tmp = _OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    os.replace(tmp, _OUT)
    cal = artifact["dcn_calibration"]
    print(f"wrote {_OUT} (effective_dcn_bytes_per_sec="
          f"{cal['effective_dcn_bytes_per_sec']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
