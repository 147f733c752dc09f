#!/usr/bin/env python
"""Chaos harness: run supervised training under injected faults and report
whether the run survived unattended (docs/FAULT_TOLERANCE.md).

For each fault spec (default: the acceptance matrix ``nan:5 hang:7
corrupt:6``) this launches ``cli supervise`` in a fresh checkpoint
directory, parses the single ordered JSON event stream the child and the
supervisor share on stdout, and writes ``CHAOS_STATUS.json``:

    {"runs": [{"fault": "corrupt:6", "ok": true, "final_step": 8,
               "restarts": 1, "rollbacks": 0, "exit_code": 0, ...}, ...],
     "ok": true}

``ok`` per run == the supervised process exited 0 AND training reached
``--steps``. Usage (CPU sim or real TPU alike):

    python tools/chaos_run.py --config configs/resnet18_cifar10.py \
        --steps 8 --out CHAOS_STATUS.json
    python tools/chaos_run.py --fault corrupt:6 --fault hang:7
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_FAULTS = ["nan:5", "hang:7", "corrupt:6"]


def build_cmd(args, fault: str, workdir: str) -> list[str]:
    cmd = [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli", "supervise",
        "--config", args.config,
        "--override", f"train.steps={args.steps}",
        "--override", "train.log_every=1",
        "--override", f"train.save_every={args.save_every}",
        "--override", f"train.checkpoint_dir={workdir}/ckpt",
        "--override", f"train.fault_injection={fault}",
        "--override", "health.enabled=True",
        "--override", f"supervisor.max_restarts={args.max_restarts}",
        "--override", "supervisor.backoff_base_s=0.2",
        "--override", "supervisor.poll_interval_s=0.2",
        "--override", f"supervisor.hang_timeout_s={args.hang_timeout}",
    ]
    for o in args.override:
        cmd += ["--override", o]
    return cmd


def run_one(args, fault: str, workdir: str) -> dict:
    cmd = build_cmd(args, fault, workdir)
    print(f"[chaos] {fault}: {' '.join(cmd)}", flush=True)
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, cwd=REPO,
            timeout=args.timeout, env=dict(os.environ),
        )
        exit_code, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        exit_code = -1
        stdout = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        stderr = "TIMEOUT"

    final_step = 0
    restarts = rollbacks = 0
    events = []
    for line in stdout.splitlines():
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "event" in rec:
            events.append(rec["event"])
            if rec["event"] == "supervisor_done":
                restarts = rec.get("restarts", 0)
            elif rec["event"] == "rollback_restart":
                rollbacks += 1
        elif "loss" in rec:
            final_step = max(final_step, int(rec.get("step", 0)))

    ok = exit_code == 0 and final_step >= args.steps
    result = {
        "fault": fault,
        "ok": ok,
        "exit_code": exit_code,
        "final_step": final_step,
        "restarts": restarts,
        "rollbacks": rollbacks,
        "events": sorted(set(events)),
    }
    if not ok:
        result["stderr_tail"] = stderr[-2000:]
    print(f"[chaos] {fault}: ok={ok} final_step={final_step} "
          f"restarts={restarts} rollbacks={rollbacks}", flush=True)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config",
                   default=os.path.join(REPO, "configs", "resnet18_cifar10.py"))
    p.add_argument("--fault", action="append", default=[],
                   help=f"repeatable fault spec (default: {DEFAULT_FAULTS})")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--save-every", type=int, default=2)
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--hang-timeout", type=float, default=120.0,
                   help="must exceed the cold-compile stall of one attempt")
    p.add_argument("--timeout", type=float, default=540.0,
                   help="wall limit per supervised run")
    p.add_argument("--override", action="append", default=[],
                   metavar="a.b=v", help="extra config overrides, e.g. the "
                   "small-model kwargs for a CPU-sim run")
    p.add_argument("--out", default=os.path.join(REPO, "CHAOS_STATUS.json"))
    args = p.parse_args(argv)

    faults = args.fault or list(DEFAULT_FAULTS)
    status: dict = {"config": args.config, "steps": args.steps, "runs": []}
    with tempfile.TemporaryDirectory(prefix="chaos_") as tmp:
        for i, fault in enumerate(faults):
            workdir = os.path.join(tmp, f"run{i}")
            os.makedirs(workdir)
            status["runs"].append(run_one(args, fault, workdir))
    status["ok"] = all(r["ok"] for r in status["runs"])
    with open(args.out, "w") as f:
        json.dump(status, f, indent=2)
        f.write("\n")
    print(f"[chaos] wrote {args.out}: ok={status['ok']}")
    return 0 if status["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
