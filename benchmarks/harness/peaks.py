"""Peak rates of the chips the benchmark has run on, keyed by the exact
``device_kind`` jax reports. A kind that is not here is an error, never a
default: add it with its source when a chip of that kind has been run."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r}: add it to "
            f"benchmarks/harness/peaks.py with its source "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]
