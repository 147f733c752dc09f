"""PROJECTED multi-chip scaling table (VERDICT r4 #9; SURVEY §6 hard part
#5: only 1 real chip is attached, so real-pod performance claims must be
clearly labeled as projected, not measured).

Method, in full (the artifact repeats it so the table is auditable):

1. Compile the REAL train step of each scenario config on the 8-device CPU
   simulator (full model size, tiny per-chip batch — the gradient-sync
   collectives are parameter-sized, so their bytes do not depend on batch).
2. Parse the compiled HLO and sum the payload bytes of every collective,
   per kind and replica-group size (``utils/hlo.collective_bytes``). Only
   dp/fsdp-group collectives (group >= 4 on the dp=8 compile) count as
   gradient sync; small tp/cp-group ops are reported but not projected.
3. Project per-chip step time at n chips as

       t_step(n) = t_compute_1chip + t_comm(n)        (conservative)
       t_step(n) = max(t_compute_1chip, t_comm(n))    (full-overlap bound)
       t_step(n) = t_compute_1chip + (1-f)*t_comm(n)  (measured overlap)

   where ``f`` is the MEASURED overlap fraction from BENCH_OVERLAP.json
   (``tools/bench_overlap.py``; the bucketed-sync subsystem,
   docs/OVERLAP.md) — the bounds stay reported, but the measured column
   replaces the old practice of quoting full overlap as if achieved.

   with ring-collective cost models
       all-reduce:      2 * B * (n-1)/n / bw
       all/reduce-gather/scatter, all-to-all: B * (n-1)/n / bw
       collective-permute: B / bw
   and, for cross-slice (DCN) scenarios, the standard hierarchical
   decomposition: intra-slice phase over ICI on the full payload, then
   cross-slice phase over DCN on payload/ici_size.

   DCN scenarios additionally carry a MEASURED-DCN column: when
   BENCH_MULTISLICE.json (``tools/bench_multislice.py``; the
   hierarchical-collective subsystem, docs/MULTISLICE.md) records a
   measured effective DCN byte rate — derivable only on a real
   multi-slice pod, null-with-reason on the CPU sim — that rate
   replaces the assumed ``DDL_DCN_GBPS``; the column is clamped into
   the [no_overlap, full_overlap] bracket the bounds define.
4. t_compute_1chip comes from the MEASURED single-chip record
   (``BENCH_BASELINE.json`` / ``TPU_NUMBERS.json``); scenarios without a
   silicon measurement get comm-time columns only, with
   ``t_compute_ms: null`` — projection without a measured base would be
   fiction twice over.

Bandwidth assumptions (stated in the artifact, adjustable via env):
  DDL_ICI_GBPS   effective per-chip ICI ring bandwidth, default 100 GB/s
                 (v5e advertises 1.6 Tbit/s aggregate ICI per chip; the
                 default assumes half of it usable per direction in a ring)
  DDL_DCN_GBPS   effective per-chip DCN bandwidth, default 6.25 GB/s
                 (25 GB/s per 4-chip v5e host, divided across its chips)

Output: PROJECTED_SCALING.json at the repo root (or $DDL_SCALING_OUT).
DDL_SCALING_SHRINK=1 compiles tiny models instead (CI dry-run of the whole
path — the numbers are then about the path, not the framework).
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

_SHRINK = os.environ.get("DDL_SCALING_SHRINK") == "1"
_OUT = os.environ.get(
    "DDL_SCALING_OUT", os.path.join(_REPO, "PROJECTED_SCALING.json")
)
ICI_GBPS = float(os.environ.get("DDL_ICI_GBPS", "100"))
DCN_GBPS = float(os.environ.get("DDL_DCN_GBPS", "6.25"))

# (config, measured-record key in BENCH_BASELINE/TPU_NUMBERS, tiny-batch
# override). gpt2_owt exercises the ZeRO-1 reduce-scatter/all-gather path;
# resnet50 the plain gradient all-reduce (BASELINE.json:2's north star).
SCENARIOS = [
    ("resnet50_imagenet", "resnet50_imagenet_images_per_sec_per_chip",
     ["data.batch_size=8"]),
    ("gpt2_owt", "gpt2_owt",
     ["data.batch_size=8", "data.seq_len=256"]),
]
_SHRINK_OVERRIDES = {
    "resnet50_imagenet": ["data.image_size=64", "model.kwargs.width=16"],
    "gpt2_owt": ["model.kwargs.size=tiny", "model.kwargs.max_len=64",
                 "data.seq_len=64", "data.vocab_size=256",
                 "train.head_chunk=32"],
}

# Projection scenarios: (label, n_chips, ici_size, n_slices).
TOPOLOGIES = [
    ("1 slice x 8 (pure ICI)", 8, 8, 1),
    ("4 slices x 8 (ICI + DCN)", 32, 8, 4),
]


def _ring_factor(kind: str, n: int) -> float:
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n
    if kind == "collective-permute":
        return 1.0
    return (n - 1) / n  # all-gather / reduce-scatter / all-to-all


def _wire_bytes(sync: dict, n: int) -> float:
    """Bytes each member actually puts on the wire for one sync, under the
    ring model — the apples-to-apples number across grad_comm modes: an
    fp32 all-reduce records its full tensor ONCE (the ring factor expands
    it), while the quantized ring's collective-permutes are already
    per-hop payloads (factor 1 each, 2(n-1) of them)."""
    return sum(
        _ring_factor(kind, n) * payload for kind, payload in sync.items()
    )


def _comm_seconds(
    sync: dict, ici: int, n_slices: int, dcn_gbps: float | None = None
) -> float:
    """Hierarchical ring model over the per-kind gradient-sync payloads.

    ``dcn_gbps`` overrides the assumed DCN bandwidth — the measured-DCN
    projections pass the BENCH_MULTISLICE.json calibration rate here."""
    if dcn_gbps is None:
        dcn_gbps = DCN_GBPS
    t = 0.0
    for kind, payload in sync.items():
        if not payload:
            continue
        # Intra-slice phase on the full payload over ICI.
        t += _ring_factor(kind, ici) * payload / (ICI_GBPS * 1e9)
        if n_slices > 1:
            # Cross-slice phase on the slice-sharded payload over DCN.
            t += _ring_factor(kind, n_slices) * (payload / ici) / (
                dcn_gbps * 1e9
            )
    return t


def _tpu_lowered_sync(name: str):
    """TPU-lowered dp-sync bytes for this config from AOT_TPU_CHECK.json
    (full-size rows only), or None. Preferred over this tool's CPU-sim
    compile when present: the CPU SPMD emitter lowers reduce-scatter as a
    full all-reduce and keeps fp32 where the TPU pipeline syncs bf16, so
    the CPU-derived comm bytes overstate ZeRO-1 traffic ~2x (both counts
    are recorded; the artifact names which one each projection used)."""
    path = os.path.join(_REPO, "AOT_TPU_CHECK.json")
    if _SHRINK or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            rows = json.load(f)
    except (json.JSONDecodeError, OSError):
        return None
    row = rows.get(name)
    if not (isinstance(row, dict) and row.get("ok")
            and not row.get("shrunk")
            and isinstance(row.get("sync_payload_bytes_by_kind"), dict)):
        return None
    raw = row["sync_payload_bytes_by_kind"]
    n0 = int(row.get("n_devices", 4))
    # Translate the lowered ops into the ring model's n-INVARIANT abstract
    # payloads (review r5: feeding geometry-baked byte counts into (n-1)/n
    # factors double-applies the topology):
    #   - all-gather/all-reduce payloads are the full tensor sizes —
    #     already n-invariant;
    #   - the TPU pipeline decomposes the grad reduce-scatter into
    #     permutes whose TOTAL is B*(n0-1)/n0 at the compile geometry n0;
    #     recover B and model it as a reduce-scatter;
    #   - all-to-all here is ACTIVATION traffic (scales with batch, e.g.
    #     the chunked-head exchange), not parameter sync: excluded.
    sync = {k: raw[k] for k in ("all-gather", "all-reduce",
                                "reduce-scatter") if raw.get(k)}
    if raw.get("collective-permute"):
        sync["reduce-scatter"] = sync.get("reduce-scatter", 0) + int(
            raw["collective-permute"] * n0 / (n0 - 1)
        )
    return sync or None


def _measured_step_seconds(name: str, key: str):
    """(t_compute seconds, provenance) from a chip record, or (None,
    reason). No training cell has been recorded on the chip yet
    (PERF_LEDGER.jsonl; ROADMAP S1), so every scenario reports bytes and
    comm-time only."""
    return None, "no chip measurement recorded yet (ROADMAP S1)"


def _measured_overlap():
    """(fraction, provenance) from BENCH_OVERLAP.json, or (None, reason).
    The canonical measured fraction is the fp32/replicated pair of the
    bench grid (the plain bucketed all-reduce the projections model); the
    per-pair table stays inspectable in that artifact."""
    path = os.environ.get(
        "DDL_OVERLAP_ARTIFACT", os.path.join(_REPO, "BENCH_OVERLAP.json")
    )
    if not os.path.exists(path):
        return None, "BENCH_OVERLAP.json not generated (tools/bench_overlap.py)"
    try:
        with open(path) as f:
            rec = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        return None, f"BENCH_OVERLAP.json unreadable: {e}"
    frac = rec.get("measured_overlap_fraction")
    if not isinstance(frac, (int, float)) or not 0.0 <= frac <= 1.0:
        return None, "no measured_overlap_fraction in BENCH_OVERLAP.json"
    return float(frac), (
        f"BENCH_OVERLAP.json: {rec.get('measured_overlap_provenance', '?')} "
        f"@ {rec.get('utc', '?')}"
    )


def _measured_dcn():
    """(effective DCN GB/s, provenance) from BENCH_MULTISLICE.json, or
    (None, reason). The calibration cell is the canonical fp32/dcn2 pair
    of the multislice bench grid (tools/bench_multislice.py): the rate is
    measurable only where flat-vs-hierarchical step times actually
    diverge — a real multi-slice pod — and the bench records
    null-with-reason on the CPU sim rather than a fabricated constant."""
    path = os.environ.get(
        "DDL_MULTISLICE_ARTIFACT",
        os.path.join(_REPO, "BENCH_MULTISLICE.json"),
    )
    if not os.path.exists(path):
        return None, (
            "BENCH_MULTISLICE.json not generated (tools/bench_multislice.py)"
        )
    try:
        with open(path) as f:
            rec = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        return None, f"BENCH_MULTISLICE.json unreadable: {e}"
    cal = rec.get("dcn_calibration")
    if not isinstance(cal, dict):
        return None, "no dcn_calibration block in BENCH_MULTISLICE.json"
    rate = cal.get("effective_dcn_bytes_per_sec")
    if not isinstance(rate, (int, float)) or rate <= 0:
        return None, cal.get(
            "reason", "no measured effective DCN rate in calibration cell"
        )
    return float(rate) / 1e9, (
        f"BENCH_MULTISLICE.json: {cal.get('cell', '?')} "
        f"@ {rec.get('utc', '?')}"
    )


def _compile_text(name: str, overrides: list) -> tuple[str, int]:
    import jax

    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config
    from distributeddeeplearning_tpu.utils.pytree import tree_bytes

    cfg = apply_overrides(
        load_config(os.path.join(_REPO, "configs", f"{name}.py")), overrides
    )
    mesh, _, trainer, dataset = build_all(cfg)
    state = trainer.init(cfg.train.seed, dataset.batch(0))
    from distributeddeeplearning_tpu.data import sharded_batches

    batch = next(iter(sharded_batches(dataset.iter_from(0), mesh)))
    text = trainer.train_step.lower(state, batch).compile().as_text()
    return text, tree_bytes(state.params)


def _precision_rows(name: str, overrides: list) -> dict:
    """Per-policy durable-state + gradient-sync bytes for this scenario
    (docs/MIXED_PRECISION.md). Each ``train.precision.policy`` either gets
    a measured row — per-member param/opt-state bytes from a REAL sharded
    init (``parallel.fsdp.per_device_bytes``) plus the analytic ring-model
    wire bytes of one grad sync — or records the composition fence by name
    (e.g. bf16_full x sgd / adamw_fused), never a silent omission. Wire
    bytes are analytic here because the CPU post-opt HLO promotes bf16
    all-reduces back to f32 (the honest 2x is HLO-asserted from the
    post-SPMD-partitioner dump in tests/test_precision.py); durable bytes
    are measured, not modeled. The fp32 row keeps each config's OWN
    ``model.kwargs.dtype`` (both scenario configs ship bf16 params — the
    legacy footgun path the policy replaces), so the fp32->bf16 delta here
    shows the cost of gaining fp32 masters, and bf16_full the moment win."""
    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config
    from distributeddeeplearning_tpu.parallel.fsdp import (
        grad_sync_bytes,
        per_device_bytes,
    )
    from distributeddeeplearning_tpu.precision import POLICIES, get_policy

    out: dict = {"per_policy": {}}
    for pol in POLICIES:
        try:
            cfg = apply_overrides(
                load_config(os.path.join(_REPO, "configs", f"{name}.py")),
                overrides + [f"train.precision.policy={pol}"],
            )
            mesh, _, trainer, dataset = build_all(cfg)
            state = trainer.init(cfg.train.seed, dataset.batch(0))
        except (ValueError, NotImplementedError) as e:
            out["per_policy"][pol] = {"fenced": f"{e}"[:200]}
            continue
        p = get_policy(pol)
        out["per_policy"][pol] = {
            "param_bytes_per_member": per_device_bytes(state.params),
            "opt_state_bytes_per_member": per_device_bytes(state.opt_state),
            "grad_sync_wire_bytes_analytic": grad_sync_bytes(
                state.params,
                mode=cfg.train.grad_comm,
                block_size=cfg.train.grad_comm_block,
                n_members=mesh.shape["dp"],
                wire_elem_bytes=(
                    p.compute_dtype.itemsize if p.mixed else None
                ),
            ),
        }
        del state
    rows = out["per_policy"]

    def _state(pol):
        r = rows.get(pol, {})
        if "fenced" in r:
            return None
        return r["param_bytes_per_member"] + r["opt_state_bytes_per_member"]

    base, full = _state("fp32"), _state("bf16_full")
    if base and full:
        out["state_bytes_fp32_over_bf16_full"] = round(base / full, 2)
    return out


def main() -> int:
    import jax

    from distributeddeeplearning_tpu.utils.hlo import collective_bytes

    n_dev = jax.device_count()
    f_overlap, overlap_prov = _measured_overlap()
    dcn_gbps_meas, dcn_prov = _measured_dcn()
    rows = []
    for name, key, overrides in SCENARIOS:
        if _SHRINK:
            overrides = overrides + _SHRINK_OVERRIDES.get(name, [])
        t0 = time.time()
        text, params_bytes = _compile_text(name, overrides)
        cb = collective_bytes(text, n_dev)
        # Gradient sync = the dp/fsdp-group collectives (group >= half the
        # sim mesh); tp/cp-group ops (group 2) are reported, not projected.
        sync = {k: sum(b for b, g in v if g >= n_dev // 2)
                for k, v in cb.items()}
        other = {k: sum(b for b, g in v if g < n_dev // 2)
                 for k, v in cb.items()}
        t_compute, provenance = _measured_step_seconds(name, key)
        tpu_sync = _tpu_lowered_sync(name)
        model_sync = tpu_sync if tpu_sync is not None else sync
        projections = []
        for label, n, ici, n_slices in TOPOLOGIES:
            t_comm = _comm_seconds(model_sync, ici, n_slices)
            proj = {
                "topology": label,
                "n_chips": n,
                "comm_ms_per_step": round(t_comm * 1e3, 3),
            }
            if t_compute:
                t_serial = t_compute + t_comm
                t_overlap = max(t_compute, t_comm)
                proj["scaling_efficiency_no_overlap"] = round(
                    t_compute / t_serial, 4
                )
                proj["scaling_efficiency_full_overlap"] = round(
                    t_compute / t_overlap, 4
                )
                if f_overlap is not None:
                    proj["scaling_efficiency_measured_overlap"] = round(
                        t_compute / (t_compute + (1.0 - f_overlap) * t_comm),
                        4,
                    )
                if n_slices > 1:
                    # Measured-DCN column: same hierarchical model, the
                    # DCN leg priced at the calibrated rate (assumed rate
                    # when the calibration is honest-null), overlap at
                    # the measured fraction, clamped into the bracket the
                    # two bounds define — hiding can't exceed full
                    # overlap, nor can calibration fall below serial.
                    t_comm_cal = _comm_seconds(
                        model_sync, ici, n_slices, dcn_gbps=dcn_gbps_meas
                    )
                    proj["comm_ms_per_step_measured_dcn"] = round(
                        t_comm_cal * 1e3, 3
                    )
                    raw = t_compute / (
                        t_compute + (1.0 - (f_overlap or 0.0)) * t_comm_cal
                    )
                    proj["scaling_efficiency_measured_dcn"] = round(
                        min(proj["scaling_efficiency_full_overlap"],
                            max(proj["scaling_efficiency_no_overlap"],
                                raw)),
                        4,
                    )
                if name == "resnet50_imagenet":
                    img_s = 256.0 / t_serial
                    proj["images_per_sec_per_chip_no_overlap"] = round(
                        img_s, 1
                    )
                    proj["images_per_sec_total_no_overlap"] = round(
                        img_s * n, 1
                    )
            projections.append(proj)
        # Compressed-gradient-sync comparison (comms_quant.py): recompile
        # the same config with grad_comm=bf16/int8 and count the ring's
        # collective-permute payloads the same way. Wire bytes (ring-model
        # per-member traffic) are the comparable number — int8 should land
        # ~4x under fp32 (1/4 the width + 1 f32 scale per 256 elements).
        # Configs the Trainer fences (non-DP meshes, grad_accum) record the
        # fence message instead of silently omitting the comparison.
        grad_comm: dict = {
            "wire_bytes_per_member": {"fp32": int(_wire_bytes(sync, n_dev))},
        }
        for gc_mode in ("bf16", "int8"):
            try:
                gc_text, _ = _compile_text(
                    name, overrides + [f"train.grad_comm={gc_mode}"]
                )
            except NotImplementedError as e:
                grad_comm["fenced"] = f"{e}"[:200]
                break
            gc_cb = collective_bytes(gc_text, n_dev)
            gc_sync = {k: sum(b for b, g in v if g >= n_dev // 2)
                       for k, v in gc_cb.items()}
            grad_comm["wire_bytes_per_member"][gc_mode] = int(
                _wire_bytes(gc_sync, n_dev)
            )
        wb = grad_comm["wire_bytes_per_member"]
        if wb.get("int8"):
            grad_comm["int8_reduction_vs_fp32"] = round(
                wb["fp32"] / wb["int8"], 2
            )
        rows.append({
            "config": name,
            "params_bytes": params_bytes,
            "grad_comm": grad_comm,
            "precision": _precision_rows(name, overrides),
            "sync_payload_bytes_by_kind": {
                k: v for k, v in sync.items() if v
            },
            "sync_payload_bytes_by_kind_tpu_lowered": tpu_sync,
            "comm_model_source": (
                "AOT_TPU_CHECK.json (TPU lowering)" if tpu_sync is not None
                else "CPU-sim compile (conservative: RS lowered as AR)"
            ),
            "non_sync_payload_bytes_by_kind": {
                k: v for k, v in other.items() if v
            },
            "t_compute_ms": round(t_compute * 1e3, 3) if t_compute else None,
            "t_compute_provenance": provenance,
            "projections": projections,
            "compile_seconds": round(time.time() - t0, 1),
        })
        tc = rows[-1]["t_compute_ms"]
        print(f"{name}: sync={sync} "
              f"t_compute={f'{tc}ms' if tc else 'unmeasured'}", flush=True)

    artifact = {
        "projected_not_measured": True,
        "method": "compiled-HLO collective byte counts on the 8-device CPU "
                  "simulator x ring-cost model x measured single-chip step "
                  "time; see tools/project_scaling.py module docstring",
        "assumptions": {
            "ici_effective_gbytes_per_sec_per_chip": ICI_GBPS,
            "dcn_effective_gbytes_per_sec_per_chip": DCN_GBPS,
            "collective_cost_model": "ring: all-reduce 2B(n-1)/n, "
                                     "gather/scatter/a2a B(n-1)/n, "
                                     "permute B",
            "hierarchical_dcn": "intra-slice ICI phase on full payload, "
                                "then cross-slice DCN phase on payload/ici",
        },
        "measured_overlap": (
            {"fraction": f_overlap, "source": overlap_prov}
            if f_overlap is not None
            else {"fraction": None, "reason": overlap_prov}
        ),
        "measured_dcn": (
            {"effective_gbytes_per_sec": dcn_gbps_meas, "source": dcn_prov}
            if dcn_gbps_meas is not None
            else {"effective_gbytes_per_sec": None, "reason": dcn_prov}
        ),
        "shrunk": _SHRINK,
        "sim_devices": n_dev,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "scenarios": rows,
    }
    tmp = _OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    os.replace(tmp, _OUT)
    print("wrote", _OUT)
    return 0


if __name__ == "__main__":
    sys.exit(main())
