"""AOT-compile every shipped config's REAL train step against a DEVICELESS
TPU v5e topology and record TPU-lowered evidence — no chip required.

Why this exists (round 5): the attached chip is wedged for most of every
round, so "the framework compiles and fits on TPU" was only evidenced for
whatever a rare healthy window reached. The deviceless topology path
(``jax.experimental.topologies.get_topology_desc`` + compile-only client,
the same mechanism ``tests/test_aot_topology.py`` uses to pin the EP
all-to-all) compiles the full-size train step with the real Mosaic/Pallas
kernels entirely on the host CPU. Per config this records:

  - ``ok``: the TPU lowering compiles at FULL model/batch size;
  - ``collectives``: payload bytes by kind from the TPU HLO
    (``utils/hlo.collective_bytes``) — unlike the CPU SPMD emitter, the
    TPU pipeline emits true reduce-scatters and async-start forms;
  - ``memory``: XLA's ``compiled.memory_analysis()`` — argument/output/
    temp/code bytes, i.e. the compiler's own HBM budget. This decides
    feasibility questions (VERDICT r4 Weak #5: "will the batch-512 MFU
    cell even fit?") from an artifact instead of a guess.

Topology: v5e:2x2 — 4 abstract chips, the smallest this environment's
libtpu can describe (its chips_per_host_bounds is fixed at 2x2; a 1x1
request is rejected) and big enough for every shipped strategy incl.
pp=4. Per-chip HBM feasibility for the 1-chip bench scenarios comes from
`@Nperchip` rows that scale the GLOBAL batch so each chip's shard equals
the single-chip shapes (memory_analysis is per-device under SPMD): the
`resnet50@512perchip` row answers whether the MFU attack's largest cell
fits the v5e's 16 GB before a healthy window is spent finding out.

Writes AOT_TPU_CHECK.json (or $DDL_AOT_OUT) incrementally (per-config,
atomic) — a crash or timeout keeps completed rows. DDL_AOT_SHRINK=1 uses
tiny models (CI dry-run of the path); DDL_AOT_ONLY=name,name filters.
Runs of this tool are CPU-only (JAX_PLATFORMS=cpu): the TPU compiler is
handed described devices, none attached. A pass is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# CPU simulation: platform and device count are read when jax is imported,
# so they are set before the first `import jax`.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

_OUT = os.environ.get(
    "DDL_AOT_OUT", os.path.join(_REPO, "AOT_TPU_CHECK.json")
)
_SHRINK = os.environ.get("DDL_AOT_SHRINK") == "1"

# (row name, config file, extra overrides). Every shipped config, plus the
# MFU attack's largest cell. File-backed variants point their data at the
# synthetic kinds for compile purposes — the input pipeline is host-side
# and does not change the compiled program.
ROWS = [
    ("resnet18_cifar10", "resnet18_cifar10", []),
    ("resnet50_imagenet", "resnet50_imagenet", []),
    ("bert_mlm", "bert_mlm", []),
    ("gpt2_owt", "gpt2_owt", []),
    ("vit_imagenet21k", "vit_imagenet21k", []),
    ("llama_lm", "llama_lm", []),
    ("gpt2_moe", "gpt2_moe", []),
    ("llama_moe", "llama_moe", []),
    ("gpt2_pp", "gpt2_pp", []),
    ("bert_pp", "bert_pp", []),
    # Per-chip-equivalent feasibility rows: global batch = 4x the 1-chip
    # bench scenario, so each of the 4 chips compiles the exact shapes the
    # real single-chip run uses.
    ("resnet50@256perchip", "resnet50_imagenet", ["data.batch_size=1024"]),
    ("resnet50@512perchip", "resnet50_imagenet", ["data.batch_size=2048"]),
    ("gpt2_owt@32perchip", "gpt2_owt", ["data.batch_size=128"]),
    ("bert_mlm@64perchip", "bert_mlm", ["data.batch_size=256"]),
    ("vit@64perchip", "vit_imagenet21k", ["data.batch_size=256"]),
    ("llama@16perchip", "llama_lm", ["data.batch_size=64"]),
    # The EP deployment shape (the shipped MoE configs default to ep=1,
    # EP being an override knob — configs/gpt2_moe.py docstring): full-size
    # evidence that the expert token exchange lowers to true all-to-alls
    # on the TPU pipeline (tiny-model version: tests/test_aot_topology.py).
    # batch 8: with dp=1 the batch is replicated per chip, and the full
    # batch 32 exhausts the compiler's HBM budget (RESOURCE_EXHAUSTED).
    ("gpt2_moe@ep4", "gpt2_moe", ["mesh.ep=4", "mesh.dp=1",
                                  "data.batch_size=8"]),
]

_TINY = {
    "resnet": ["data.batch_size=8", "data.image_size=64"],
    "lm": ["model.kwargs.size=tiny", "model.kwargs.max_len=64",
           "data.batch_size=8", "data.seq_len=64", "data.vocab_size=256",
           "train.head_chunk=32"],
    "bert": ["model.kwargs.size=tiny", "model.kwargs.max_len=64",
             "data.batch_size=8", "data.seq_len=64", "data.vocab_size=256",
             "train.head_chunk=32"],
    "vit": ["model.kwargs.size=tiny", "data.batch_size=8",
            "data.image_size=32", "model.kwargs.image_size=32",
            "model.kwargs.patch_size=8"],
}


def _shrink_overrides(cfg_name: str) -> list:
    if cfg_name.startswith("resnet"):
        return _TINY["resnet"]
    if cfg_name.startswith("vit"):
        return _TINY["vit"]
    if cfg_name.startswith("bert"):
        return _TINY["bert"]
    return _TINY["lm"]


def _rows():
    only = os.environ.get("DDL_AOT_ONLY")
    rows = ROWS
    if only:
        names = [n.strip() for n in only.split(",") if n.strip()]
        known = {r[0] for r in ROWS}
        unknown = [n for n in names if n not in known]
        if unknown:
            raise SystemExit(f"DDL_AOT_ONLY names unknown rows: {unknown}")
        rows = [r for r in ROWS if r[0] in names]
    if _SHRINK:
        rows = [(name, cfg, ov + _shrink_overrides(cfg))
                for name, cfg, ov in rows]
    return rows


def _topology_devices(name: str):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name=name)
    return list(topo.devices)


def _compile_row(cfg_name: str, overrides: list, devices) -> dict:
    """Compile the config's train step for the given abstract devices;
    return {collectives, memory, hlo_bytes} — nothing is materialized
    (eval_shape setup + ShapeDtypeStruct batch)."""
    from distributeddeeplearning_tpu.cli import build_all
    from distributeddeeplearning_tpu.config import apply_overrides, load_config
    from distributeddeeplearning_tpu.utils.hlo import collective_bytes

    cfg = apply_overrides(
        load_config(os.path.join(_REPO, "configs", f"{cfg_name}.py")),
        overrides,
    )
    # Force the synthetic data kinds: file-backed pipelines are host-side
    # and irrelevant to the compiled program (and their files may not
    # exist in this checkout).
    if cfg.data.kind == "record_file_image":
        cfg = apply_overrides(cfg, ["data.kind=synthetic_image"])
    elif cfg.data.kind == "record_file_tokens":
        cfg = apply_overrides(cfg, ["data.kind=synthetic_tokens"])
    _, _, trainer, ds = build_all(cfg, devices=devices)
    compiled = trainer.lower_train_step(ds.batch(0)).compile()
    text = compiled.as_text()
    n_dev = len(devices)
    cb = collective_bytes(text, n_dev)
    ma = compiled.memory_analysis()
    mem = {
        k: int(getattr(ma, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes")
        if hasattr(ma, k)
    }
    if mem:
        # The compiler's own per-chip HBM budget for a step: live args +
        # outputs (minus donated/aliased) + temporaries + program.
        mem["est_peak_hbm_bytes"] = (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            - mem.get("alias_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
            + mem.get("generated_code_size_in_bytes", 0)
        )
    return {
        "collective_payload_bytes_by_kind": {
            k: sum(b for b, _ in v) for k, v in cb.items() if v
        },
        # FULL-mesh-group traffic (the dp/fsdp axes on these compiles) vs
        # tp/ep/cp subgroup ops, from the AUTHORITATIVE TPU lowering (the
        # CPU SPMD emitter lowers reduce-scatter as all-reduce and keeps
        # fp32 where the TPU pipeline syncs bf16). Caveat: permutes carry
        # no replica_groups and default to full-mesh, so rows whose mesh
        # has pp/cp axes count stage/ring permutes here too — fine for
        # the dp-only projection scenarios, misleading for pp rows.
        "n_devices": n_dev,
        "sync_payload_bytes_by_kind": {
            k: sum(b for b, g in v if g >= n_dev)
            for k, v in cb.items() if v
        },
        "memory": mem,
        "hlo_bytes": len(text),
    }


def main() -> int:
    from distributeddeeplearning_tpu.utils.compat import setup_compile_cache

    setup_compile_cache()
    recs = {}
    if os.path.exists(_OUT):
        try:
            with open(_OUT) as f:
                recs = json.load(f)
        except (json.JSONDecodeError, OSError):
            recs = {}

    def dump():
        tmp = _OUT + ".tmp"
        with open(tmp, "w") as f:
            json.dump(recs, f, indent=2)
            f.write("\n")
        os.replace(tmp, _OUT)

    recs["_meta"] = {
        "method": "deviceless AOT compile via jax.experimental.topologies "
                  "(see module docstring); nothing ran on hardware",
        "shrunk": _SHRINK,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # Rows renamed/removed from ROWS must not persist as stale evidence
    # (review r5): drop any stored key this version of the tool doesn't
    # know about.
    known = {r[0] for r in ROWS}
    for stale in [k for k in recs if not k.startswith("_")
                  and k not in known]:
        del recs[stale]
    failures = 0
    topo = "v5e:2x2"
    for name, cfg_name, overrides in _rows():
        # Per-row shrunk/utc: a partial re-run must not let _meta (which
        # describes only the LAST run) misrepresent rows written earlier
        # under different settings (review r5).
        row = {"config": cfg_name, "overrides": overrides,
               "topology": topo, "shrunk": _SHRINK,
               "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
        t0 = time.time()
        try:
            try:
                devices = _topology_devices(topo)
            except Exception as e:
                # A SIGKILLed libtpu process leaves a stale lockfile that
                # aborts every later compile-only client ("Internal error
                # when accessing libtpu multi-process lockfile") — the
                # error's own remedy, applied once.
                if "libtpu_lockfile" not in str(e):
                    raise
                os.remove("/tmp/libtpu_lockfile")
                devices = _topology_devices(topo)
            out = _compile_row(cfg_name, overrides, devices)
            out["compile_seconds"] = round(time.time() - t0, 1)
            row.update(ok=True, **out)
        except Exception as e:
            row.update(ok=False, error=f"{type(e).__name__}: {e}"[:400])
            failures += 1
            traceback.print_exc()
        print(f"{name}: {'ok' if row['ok'] else row['error'][:80]}",
              flush=True)
        recs[name] = row
        dump()
    print("wrote", _OUT, f"({failures} failures)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
