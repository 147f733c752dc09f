"""Telemetry self-measurement -> TELEMETRY.json + FLEET.json.

Two questions the telemetry subsystem (telemetry.py, docs/
OBSERVABILITY.md) must answer about ITSELF, measured on the 8-device
CPU sim with a real ``fit`` loop (GPT-2 tiny, adamw, synthetic tokens):

1. **What does it cost?** The instrumented loop (spans + ledger + event
   mirror) vs the identical loop with telemetry off, interleaved
   disabled/enabled segments through ONE warm process (same jit cache,
   same dataset), median over segments. The acceptance bar is
   ``overhead_fraction <= 0.02`` of steps/s — telemetry that slows the
   loop isn't observability, it's interference.

2. **What does it see?** One enabled run's artifacts, verified: the
   Chrome trace is structurally valid (``validate_chrome_trace``), the
   goodput ledger's categories sum to its measured wall clock within
   1%, and the device registry carries a non-null ``memory_analysis``
   for the AOT-compiled train step (the compiler's argument/output/temp
   buffer accounting — reported even by the CPU backend). The AOT
   compile is paid HERE, where the cost is acknowledged, not in fit
   (the AOT path does not share the traced-call cache on this jax).

A failed or invalid run never clobbers committed artifacts: both files
are written atomically only after every check passed. ``--check``
validates an existing TELEMETRY.json instead of re-measuring (CI /
test-pin mode).

Since the fleet layer (telemetry_aggregate.py, docs/OBSERVABILITY.md)
there is a third question: **does aggregation work on a REAL multi-
process run?** ``measure()`` ends with a fleet rehearsal — an actual
2-child ``cli launch --independent`` CPU-sim run into one shared
telemetry dir, aggregated by ``build_fleet`` — and asserts the fleet
invariants (merged trace valid, pod goodput categories sum exactly to
aggregate wall, straggler report over common steps, per-process
histograms merged) before anything is written. The resulting FLEET.json
is copied to the repo root (committed artifact; ``$DDL_FLEET_OUT``),
and the aggregation pass's wall time is recorded against the same 2%
bar (aggregation that costs a meaningful fraction of the run it
describes would be interference, same principle as the loop overhead).

Usage: python tools/telemetry_report.py            (measure + write)
       python tools/telemetry_report.py --check    (validate committed)
Env: $DDL_TELEMETRY_OUT / $DDL_FLEET_OUT
override the output paths; $DDL_TELEMETRY_STEPS sets the per-segment
step count; DDL_TELEMETRY_SHRINK=1 is the CI dry-run (short segments).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# CPU simulation: platform and device count are read when jax is imported,
# so they are set before the first `import jax`.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

_SHRINK = os.environ.get("DDL_TELEMETRY_SHRINK") == "1"
_OUT = os.environ.get(
    "DDL_TELEMETRY_OUT", os.path.join(_REPO, "TELEMETRY.json")
)
_SEG_STEPS = int(os.environ.get(
    "DDL_TELEMETRY_STEPS", "16" if _SHRINK else "32"
))
_SEGMENTS = 2 if _SHRINK else 7  # disabled/enabled pairs
_OVERHEAD_BAR = 0.02
_LEDGER_TOL = 0.01  # categories must sum to wall within 1%
_FLEET_OUT = os.environ.get(
    "DDL_FLEET_OUT", os.path.join(_REPO, "FLEET.json")
)
_FLEET_STEPS = int(os.environ.get(
    "DDL_FLEET_STEPS", "12" if _SHRINK else "24"
))
# Pod goodput exactness: each per-attempt record commits 6-decimal
# rounding, so N summed records can drift by N microseconds — never more.
_FLEET_SUM_TOL = 1e-5


def _workload():
    """(trainer, dataset, state) — GPT-2 tiny on synthetic tokens, a
    cheap-step workload (dispatch-bound, so per-step host overhead is
    MAXIMALLY visible — an honest worst case for the overhead bar)."""
    import jax

    from distributeddeeplearning_tpu import data as data_lib
    from distributeddeeplearning_tpu import models
    from distributeddeeplearning_tpu.mesh import MeshConfig, build_mesh
    from distributeddeeplearning_tpu.train import (
        Trainer,
        get_task,
        make_optimizer,
    )

    mesh = build_mesh(MeshConfig(dp=8))
    model = models.get_model(
        "gpt2", size="tiny", max_len=64, vocab_size=256, dropout_rate=0.0
    )
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh
    )
    dataset = data_lib.make_dataset(
        "synthetic_tokens", batch_size=16, seq_len=64, vocab_size=256,
        seed=0, n_distinct=4,
    )
    state = trainer.init(0, dataset.batch(0))
    return mesh, trainer, dataset, state


def _fit_segment(trainer, dataset, mesh, state, n_steps, telemetry):
    """Run ``n_steps`` more steps through the REAL fit loop (continuing
    from ``state.step``), returning (new_state, elapsed_s)."""
    import jax

    from distributeddeeplearning_tpu import data as data_lib
    from distributeddeeplearning_tpu.train import fit

    start = int(state.step)
    batches = data_lib.sharded_batches(dataset.iter_from(start), mesh)
    t0 = time.perf_counter()
    state, _ = fit(
        trainer, state, batches, steps=start + n_steps,
        log_every=max(n_steps // 4, 1), log_fn=lambda m: None,
        telemetry=telemetry,
    )
    jax.block_until_ready(state.params)
    return state, time.perf_counter() - t0


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def measure() -> tuple[dict, dict]:
    """(telemetry_artifact, fleet_artifact) — raises on any failed
    internal check so main() can refuse to write."""
    import jax

    from distributeddeeplearning_tpu.telemetry import (
        Telemetry,
        read_goodput,
        validate_chrome_trace,
    )

    mesh, trainer, dataset, state = _workload()
    tdir = tempfile.mkdtemp(prefix="ddl_telemetry_report_")

    # Warmup: compile + settle, telemetry off.
    state, _ = _fit_segment(trainer, dataset, mesh, state, 8, None)

    tel = Telemetry(out_dir=tdir, ring_size=4096)
    dis, en = [], []
    for i in range(_SEGMENTS):
        # Alternate which mode runs first within each pair, so slow
        # machine-level drift (load, thermal) cancels instead of biasing
        # one mode — the per-step instrumentation cost is microseconds
        # against ~ms steps, so drift IS the dominant error term.
        order = ("dis", "en") if i % 2 == 0 else ("en", "dis")
        for mode in order:
            if mode == "dis":
                state, dt = _fit_segment(
                    trainer, dataset, mesh, state, _SEG_STEPS, None
                )
                dis.append(_SEG_STEPS / dt)
            else:
                tel.ledger.open(int(state.step))
                state, dt = _fit_segment(
                    trainer, dataset, mesh, state, _SEG_STEPS, tel
                )
                tel.ledger.close(int(state.step))
                en.append(_SEG_STEPS / dt)
        print(f"pair {i}: disabled {dis[-1]:.2f} steps/s, "
              f"enabled {en[-1]:.2f} steps/s", flush=True)
    disabled_sps, enabled_sps = _median(dis), _median(en)
    overhead = max(1.0 - enabled_sps / disabled_sps, 0.0)

    # -- artifact checks (all must pass before anything is written) -----
    problems: list[str] = []

    tel.write_trace()
    with open(tel.trace_path) as f:
        trace = json.load(f)
    trace_problems = validate_chrome_trace(trace)
    if trace_problems:
        problems.append(f"invalid chrome trace: {trace_problems[:3]}")
    span_names = sorted({
        ev.get("name") for ev in trace["traceEvents"] if ev.get("ph") == "B"
    })

    ledger_checks = []
    for rec in read_goodput(tel.ledger.path):
        if rec.get("record") != "attempt":
            continue
        wall = float(rec["wall_s"])
        total = sum(float(v) for v in rec["categories"].values())
        err = abs(total - wall) / wall if wall else 0.0
        ledger_checks.append(round(err, 8))
        if err > _LEDGER_TOL:
            problems.append(
                f"ledger categories sum {total} vs wall {wall} "
                f"(err {err:.4f} > {_LEDGER_TOL})"
            )
    if not ledger_checks:
        problems.append("no ledger attempt records")

    # The device registry's memory probe: ONE acknowledged AOT compile
    # against the placed batch the traced step ran on.
    from distributeddeeplearning_tpu import data as data_lib

    placed = next(iter(
        data_lib.sharded_batches(dataset.iter_from(0), mesh)
    ))
    tel.record_compile(
        "train_step_aot", trainer.train_step, state, placed, donated_args=1
    )
    exe = tel.registry.get("train_step_aot")
    ma = (exe or {}).get("memory_analysis")
    required_nonnull = ("argument_bytes", "output_bytes", "temp_bytes")
    if not ma:
        problems.append("memory_analysis is null for the AOT step")
    else:
        for key in required_nonnull:
            if not isinstance(ma.get(key), int) or ma[key] <= 0:
                problems.append(f"memory_analysis.{key} not a positive int")

    if overhead > _OVERHEAD_BAR:
        problems.append(
            f"overhead_fraction {overhead:.4f} > {_OVERHEAD_BAR} bar"
        )
    if problems:
        raise RuntimeError("; ".join(problems))

    # The fleet rehearsal (raises on any violated invariant): a real
    # 2-child launch, aggregated. Runs LAST so its artifacts only get
    # written when the single-process story already checked out.
    print("fleet rehearsal: 2-child cli launch --independent ...",
          flush=True)
    fleet, fleet_run = fleet_rehearsal()

    utc = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    telemetry_art = {
        "schema": 1,
        "workload": "gpt2 tiny (vocab 256, seq 64) x adamw, synthetic "
                    "tokens, cpu-sim dp=8, real fit() segments",
        "sim_devices": jax.device_count(),
        "segment_steps": _SEG_STEPS,
        "segments": _SEGMENTS,
        "shrunk": _SHRINK,
        "overhead": {
            "disabled_steps_per_sec": round(disabled_sps, 4),
            "enabled_steps_per_sec": round(enabled_sps, 4),
            "overhead_fraction": round(overhead, 6),
            "bar": _OVERHEAD_BAR,
            "disabled_steps_per_sec_all": [round(v, 4) for v in dis],
            "enabled_steps_per_sec_all": [round(v, 4) for v in en],
        },
        "trace": {
            "events": len(trace["traceEvents"]),
            "valid": True,
            "span_names": span_names,
        },
        "ledger": {
            "attempts": len(ledger_checks),
            "sum_vs_wall_rel_err": ledger_checks,
            "tolerance": _LEDGER_TOL,
        },
        "registry": tel.registry.to_dict(),
        "fleet": {**fleet_run, "headline": fleet["headline"]},
        "utc": utc,
    }
    return telemetry_art, fleet


_FLEET_CFG = '''\
"""Fleet-rehearsal workload (generated by tools/telemetry_report.py)."""
from distributeddeeplearning_tpu.config import (
    Config, DataConfig, ModelConfig, OptimConfig, TrainConfig,
)
from distributeddeeplearning_tpu.mesh import MeshConfig


def get_config() -> Config:
    return Config(
        model=ModelConfig(
            name="gpt2",
            kwargs={{"size": "tiny", "vocab_size": 256, "max_len": 64,
                     "dropout_rate": 0.0}},
        ),
        data=DataConfig(
            kind="synthetic_tokens", batch_size=8, seq_len=64,
            vocab_size=256, seed=0,
        ),
        optim=OptimConfig(name="adamw", lr=1e-3),
        train=TrainConfig(steps={steps}, log_every={log_every}, task="lm"),
        mesh=MeshConfig(dp=-1),
    )
'''


def fleet_rehearsal() -> tuple[dict, dict]:
    """A REAL 2-child ``cli launch --independent`` CPU-sim run into one
    shared telemetry dir, then the full aggregation pass.

    Returns ``(fleet_record, run_info)`` and raises on any violated
    fleet invariant — so a broken aggregator can never write artifacts.
    ``--independent`` because the multiprocess CPU rendezvous needs
    jax >= 0.5 (docs/MULTISLICE.md); the telemetry-dir sharing, artifact
    stamping, and clock alignment under test are identical either way."""
    import subprocess

    from distributeddeeplearning_tpu.telemetry_aggregate import build_fleet

    work = tempfile.mkdtemp(prefix="ddl_fleet_rehearsal_")
    tdir = os.path.join(work, "telemetry")
    cfg_path = os.path.join(work, "fleet_cfg.py")
    with open(cfg_path, "w") as f:
        f.write(_FLEET_CFG.format(
            steps=_FLEET_STEPS, log_every=max(_FLEET_STEPS // 4, 1)
        ))
    cmd = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli", "launch",
        "--config", cfg_path, "--num-processes", "2",
        "--devices-per-process", "2", "--independent",
        "--telemetry", tdir,
    ]
    t0 = time.perf_counter()
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    run_wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet launch exited {proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    t1 = time.perf_counter()
    fleet = build_fleet(tdir)
    agg_wall = time.perf_counter() - t1

    problems: list[str] = []
    if fleet["processes"] != [0, 1]:
        problems.append(f"expected processes [0, 1], got {fleet['processes']}")
    if not fleet["trace"]["valid"] or not fleet["trace"]["events"]:
        problems.append(
            f"merged trace invalid/empty: {fleet['trace']['problems']}"
        )
    gp = fleet["goodput"]
    if not gp or gp.get("attempts", 0) < 2:
        problems.append(f"pod goodput missing/short: {gp}")
    else:
        drift = abs(sum(gp["categories"].values()) - gp["wall_s"])
        if drift > _FLEET_SUM_TOL:
            problems.append(
                f"pod categories sum off wall by {drift} > {_FLEET_SUM_TOL}"
            )
        if not (0.0 < gp["goodput_fraction"] <= 1.0):
            problems.append(
                f"pod goodput_fraction {gp['goodput_fraction']} out of (0,1]"
            )
    st = fleet["straggler"]
    if st["common_steps"] < _FLEET_STEPS:
        problems.append(
            f"straggler report covers {st['common_steps']} common steps "
            f"< {_FLEET_STEPS}"
        )
    elif not st["skew_s"] or st["skew_s"]["max"] < 0:
        problems.append(f"straggler skew malformed: {st['skew_s']}")
    hist = fleet["histograms"].get("step")
    if not hist or hist["count"] < 2 * _FLEET_STEPS:
        problems.append(
            f"merged step histogram count {hist and hist['count']} < "
            f"{2 * _FLEET_STEPS} (2 processes x {_FLEET_STEPS} steps)"
        )
    agg_frac = agg_wall / run_wall if run_wall else 0.0
    if agg_frac > _OVERHEAD_BAR:
        problems.append(
            f"aggregation wall {agg_wall:.3f}s is {agg_frac:.4f} of the "
            f"run ({run_wall:.1f}s) > {_OVERHEAD_BAR} bar"
        )
    if problems:
        raise RuntimeError("fleet rehearsal: " + "; ".join(problems))
    run_info = {
        "num_processes": 2,
        "steps_per_process": _FLEET_STEPS,
        "independent": True,
        "run_wall_s": round(run_wall, 3),
        "aggregation_wall_s": round(agg_wall, 4),
        "aggregation_overhead_fraction": round(agg_frac, 6),
        "bar": _OVERHEAD_BAR,
    }
    return fleet, run_info


def check(path: str = _OUT) -> list[str]:
    """Validate a committed TELEMETRY.json; returns problems (empty ==
    valid). This is the test-pinned contract of the artifact."""
    problems: list[str] = []
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable: {type(e).__name__}: {e}"]
    ov = art.get("overhead") or {}
    frac = ov.get("overhead_fraction")
    if not isinstance(frac, (int, float)):
        problems.append("overhead.overhead_fraction missing")
    elif frac > float(ov.get("bar", _OVERHEAD_BAR)):
        problems.append(f"overhead_fraction {frac} exceeds bar")
    if not (art.get("trace") or {}).get("valid"):
        problems.append("trace.valid is not true")
    led = art.get("ledger") or {}
    errs = led.get("sum_vs_wall_rel_err")
    if not errs:
        problems.append("ledger.sum_vs_wall_rel_err missing/empty")
    elif any(e > float(led.get("tolerance", _LEDGER_TOL)) for e in errs):
        problems.append("a ledger attempt exceeds the sum-vs-wall tolerance")
    exes = (art.get("registry") or {}).get("executables") or {}
    mas = [e.get("memory_analysis") for e in exes.values()
           if isinstance(e, dict)]
    good = [
        ma for ma in mas
        if isinstance(ma, dict) and all(
            isinstance(ma.get(k), int) and ma[k] > 0
            for k in ("argument_bytes", "output_bytes", "temp_bytes")
        )
    ]
    if not good:
        problems.append(
            "no registry executable with non-null positive "
            "argument/output/temp memory_analysis bytes"
        )
    fl = art.get("fleet") or {}
    if not isinstance(fl.get("aggregation_overhead_fraction"), (int, float)):
        problems.append("fleet.aggregation_overhead_fraction missing")
    elif fl["aggregation_overhead_fraction"] > float(
        fl.get("bar", _OVERHEAD_BAR)
    ):
        problems.append("fleet aggregation overhead exceeds bar")
    return problems


def check_fleet(path: str = _FLEET_OUT) -> list[str]:
    """Validate a committed FLEET.json (the fleet-rehearsal artifact) —
    the test-pinned schema + invariants, re-checked without re-running."""
    problems: list[str] = []
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable: {type(e).__name__}: {e}"]
    if art.get("schema_version") != 1:
        problems.append(f"schema_version {art.get('schema_version')} != 1")
    if not isinstance(art.get("processes"), list) or len(
        art.get("processes") or []
    ) < 2:
        problems.append("fewer than 2 processes in FLEET.json")
    tr = art.get("trace") or {}
    if not tr.get("valid") or not tr.get("events"):
        problems.append("merged trace not valid/non-empty")
    gp = art.get("goodput") or {}
    cats = gp.get("categories") or {}
    if not cats:
        problems.append("pod goodput categories missing")
    elif abs(sum(cats.values()) - float(gp.get("wall_s", 0.0))) \
            > _FLEET_SUM_TOL:
        problems.append("pod categories do not sum to aggregate wall")
    st = art.get("straggler") or {}
    if not st.get("common_steps"):
        problems.append("straggler report has no common steps")
    elif not isinstance((st.get("skew_s") or {}).get("max"), (int, float)):
        problems.append("straggler skew_s.max missing")
    hl = art.get("headline") or {}
    for k in ("pod_goodput_fraction", "max_step_skew_s"):
        if not isinstance(hl.get(k), (int, float)):
            problems.append(f"headline.{k} missing")
    if not art.get("histograms"):
        problems.append("merged histograms missing")
    return problems


def _write(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--check" in argv:
        problems = [f"TELEMETRY: {p}" for p in check()]
        problems += [f"FLEET: {p}" for p in check_fleet()]
        if problems:
            print("committed telemetry artifacts INVALID:", file=sys.stderr)
            for p in problems:
                print(f"  - {p}", file=sys.stderr)
            return 1
        print(f"{_OUT} and {_FLEET_OUT} valid")
        return 0
    try:
        telemetry_art, fleet = measure()
    except Exception as e:
        # Refuse to clobber committed artifacts with a failed run.
        print(f"measurement FAILED ({type(e).__name__}: {e}); leaving "
              f"{_OUT} and {_FLEET_OUT} untouched",
              file=sys.stderr)
        raise
    _write(_OUT, telemetry_art)
    _write(_FLEET_OUT, fleet)
    ov = telemetry_art["overhead"]
    print(f"wrote {_OUT} and {_FLEET_OUT} "
          f"(overhead_fraction={ov['overhead_fraction']}, "
          f"enabled {ov['enabled_steps_per_sec']} vs disabled "
          f"{ov['disabled_steps_per_sec']} steps/s; pod goodput "
          f"{fleet['headline']['pod_goodput_fraction']}, max step skew "
          f"{fleet['headline']['max_step_skew_s']}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
