"""Dry-run of the projected-scaling tool (VERDICT r4 #9).

``tools/project_scaling.py`` compiles real train steps on the CPU sim,
counts collective bytes from the HLO, and writes PROJECTED_SCALING.json.
Like the harvest tools, its whole path runs here in shrink mode so a
latent bug can't surface only when the artifact is regenerated — and the
committed artifact (when present) is sanity-asserted.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "project_scaling.py")
_ARTIFACT = os.path.join(_REPO, "PROJECTED_SCALING.json")


@pytest.fixture(scope="module")
def shrunk(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("scaling")
    out = tmp_path / "PROJECTED_SCALING.json"
    env = dict(os.environ)
    env.update(DDL_SCALING_SHRINK="1", DDL_SCALING_OUT=str(out))
    proc = subprocess.run(
        [sys.executable, _TOOL], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(out.read_text())


def test_shrunk_artifact_wellformed(shrunk):
    assert shrunk["projected_not_measured"] is True
    assert shrunk["shrunk"] is True
    assert shrunk["assumptions"]["ici_effective_gbytes_per_sec_per_chip"] > 0
    names = [r["config"] for r in shrunk["scenarios"]]
    assert names == ["resnet50_imagenet", "gpt2_owt"]


def test_dp_scenario_counts_gradient_allreduce(shrunk):
    rn = shrunk["scenarios"][0]
    # Pure-DP resnet: the sync traffic is the gradient all-reduce, and it
    # is parameter-sized (fp32 grads) — the byte counter must land within
    # 2x of 4*params (BN stats psums ride along; nothing param-sized may
    # be missing).
    ar = rn["sync_payload_bytes_by_kind"].get("all-reduce", 0)
    assert ar >= 4 * rn["params_bytes"] / 4  # >= params fp32 once
    assert ar <= 3 * 4 * rn["params_bytes"]


def test_zero1_scenario_emits_gather_traffic(shrunk):
    gpt = shrunk["scenarios"][1]
    # ZeRO-1: updated params are re-gathered every step (the CPU emitter
    # lowers the reduce-scatter side as all-reduce + slice, so the gather
    # side is the stable assertion).
    assert gpt["sync_payload_bytes_by_kind"].get("all-gather", 0) > 0


def test_grad_comm_comparison_shows_int8_win(shrunk):
    # The compressed-sync comparison (comms_quant.py): every row either
    # carries ring-model wire bytes for all three modes with the designed
    # ordering, or records the Trainer's composition fence by name — never
    # a silently missing comparison.
    for row in shrunk["scenarios"]:
        gc = row["grad_comm"]
        wb = gc["wire_bytes_per_member"]
        assert wb["fp32"] > 0
        if "fenced" in gc:
            assert "grad_comm" in gc["fenced"]
            continue
        assert wb["fp32"] > wb["bf16"] > wb["int8"] > 0
        assert gc["int8_reduction_vs_fp32"] > 1.5, gc
    # The ~4x design number is pinned on the pure-DP resnet row, where the
    # fp32 baseline is exactly one param-sized ring all-reduce. (The zero1
    # gpt2 row's fp32 baseline carries the CPU emitter's overstated RS
    # lowering, so its ratio reads high — the tool documents that caveat.)
    rn = shrunk["scenarios"][0]["grad_comm"]
    assert "fenced" not in rn, rn
    assert 3.0 < rn["int8_reduction_vs_fp32"] < 4.5, rn


def test_precision_rows_cover_every_policy(shrunk):
    # Mixed-precision comparison (docs/MIXED_PRECISION.md): every scenario
    # carries a per-policy block — measured per-member durable bytes from a
    # real sharded init plus analytic ring-model sync bytes — or records
    # the composition fence by name, never a silent omission.
    for row in shrunk["scenarios"]:
        pp = row["precision"]["per_policy"]
        assert set(pp) == {"fp32", "bf16", "bf16_full"}
        for pol in ("fp32", "bf16"):
            assert pp[pol]["param_bytes_per_member"] > 0
            assert pp[pol]["opt_state_bytes_per_member"] > 0
            assert pp[pol]["grad_sync_wire_bytes_analytic"] > 0
        # Grads travel in the compute dtype: the modeled sync payload
        # halves under bf16 (both scenario configs sync grad_comm=fp32).
        assert pp["fp32"]["grad_sync_wire_bytes_analytic"] == pytest.approx(
            2 * pp["bf16"]["grad_sync_wire_bytes_analytic"], rel=0.01
        )
        assert "fenced" in pp["bf16_full"]
    # Both shipped scenario optimizers fence bf16_full by name: low-precision
    # moments are an Adam state layout (sgd) and the Pallas kernel's moment
    # buffers are fp32 (adamw_fused).
    scen = shrunk["scenarios"]
    assert "sgd" in scen[0]["precision"]["per_policy"]["bf16_full"]["fenced"]
    assert "adamw_fused" in (
        scen[1]["precision"]["per_policy"]["bf16_full"]["fenced"]
    )


def test_dcn_projection_costs_more_than_ici(shrunk):
    for row in shrunk["scenarios"]:
        ici, dcn = row["projections"]
        assert dcn["n_chips"] > ici["n_chips"]
        assert dcn["comm_ms_per_step"] > ici["comm_ms_per_step"]


def test_measured_base_present_only_with_silicon_record(shrunk):
    # No training cell has a chip record yet (ROADMAP S1): a projection
    # without a measured compute base would be fiction, so every scenario
    # reports bytes and comm time only, and says why.
    for row in shrunk["scenarios"]:
        assert row["t_compute_ms"] is None
        assert "no chip measurement" in row["t_compute_provenance"]
        for proj in row["projections"]:
            assert proj["comm_ms_per_step"] > 0
            assert not any(k.startswith("scaling_efficiency") for k in proj)
            assert "images_per_sec_per_chip_no_overlap" not in proj


def test_measured_overlap_feeds_projection(shrunk):
    # The measured overlap fraction (BENCH_OVERLAP.json, docs/OVERLAP.md)
    # replaces the assumed full-overlap number: when the bench artifact is
    # present, every projection with a measured compute base carries a
    # measured-overlap efficiency bracketed by the two bounds.
    mo = shrunk["measured_overlap"]
    if mo["fraction"] is None:
        assert mo["reason"]  # absence is named, never silent
        pytest.skip("BENCH_OVERLAP.json not generated")
    assert 0.0 <= mo["fraction"] <= 1.0
    assert "BENCH_OVERLAP.json" in mo["source"]
    # The bracketed efficiency needs a measured compute base; none exists.
    for proj in shrunk["scenarios"][0]["projections"]:
        assert "scaling_efficiency_measured_overlap" not in proj


def test_measured_dcn_calibration_feeds_projection(shrunk):
    # The DCN calibration (BENCH_MULTISLICE.json, docs/MULTISLICE.md):
    # either a measured effective rate with provenance or a named reason
    # (the CPU sim can't measure DCN), never silence — and every DCN
    # projection with a measured compute base carries a measured-DCN
    # efficiency bracketed by the serial / full-overlap bounds.
    md = shrunk["measured_dcn"]
    if md["effective_gbytes_per_sec"] is None:
        assert md["reason"]
    else:
        assert md["effective_gbytes_per_sec"] > 0
        assert "BENCH_MULTISLICE.json" in md["source"]
    ici_proj, dcn_proj = shrunk["scenarios"][0]["projections"]
    assert "scaling_efficiency_measured_dcn" not in ici_proj  # DCN-only
    assert "scaling_efficiency_measured_dcn" not in dcn_proj  # no base yet


def test_committed_artifact_is_full_size():
    if not os.path.exists(_ARTIFACT):
        pytest.skip("PROJECTED_SCALING.json not yet generated")
    with open(_ARTIFACT) as f:
        rec = json.load(f)
    assert rec["projected_not_measured"] is True
    assert rec["shrunk"] is False  # the committed table is never a dry-run
    rn = rec["scenarios"][0]
    # Full ResNet-50: ~25.6M params -> the gradient all-reduce must be
    # ~100 MB of fp32, not a shrunken model's.
    assert rn["params_bytes"] > 80e6
    assert rn["sync_payload_bytes_by_kind"]["all-reduce"] > 80e6
