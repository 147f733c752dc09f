#!/usr/bin/env python
"""Serving chaos harness: kill, wedge, and mute REAL fleet workers under
load and pin that the self-healing serving fleet (serving/
fleet_supervisor.py) keeps its promises. Writes SERVE_CHAOS_STATUS.json.

One run per fault class (``serving.fault_injection``, armed on worker 0
via ``$DDL_SERVE_FAULT_WORKER``):

- ``worker_crash:K`` — ``os._exit(EXIT_FAULT)`` at engine step K: no
  drain, no flush, no goodbye. Detected by child exit; the LAST periodic
  spill checkpoint (``serving.spill_checkpoint_every_s``) is what the
  restarted worker re-warms from.
- ``worker_hang:K`` — the loop freezes with the process alive. Detected
  by the router's stale-heartbeat sweep; the supervisor SIGKILLs (a hung
  worker cannot honor SIGTERM's drain contract) and restarts.
- ``conn_drop:K`` — the worker severs the router socket. Detected as
  EOF/ProtocolError on the parent's pump; the orphaned worker drains
  and exits on its own.
- ``heartbeat_stall:K`` — the worker KEEPS SERVING but goes
  heartbeat-silent: the half-dead case. The router quarantines it on
  the stale sweep and retries its work on the survivor under a bumped
  attempt epoch, so any late result frames from the stalled attempt
  are discarded by epoch — never double-delivered.

Every run drives the same two-wave shared-prefix workload (prefix
cache + spill tier, device pool constrained below the prefix working set so the spill tier is hot) over
a 2-worker fleet, waits for the supervisor to detect + restart, then
submits wave B so the restarted worker serves real post-recovery load
from its re-warmed cache. Pins per run:

- exactly-once accounting: ``served + shed + dropped == submitted`` and
  ``duplicate_deliveries == 0``;
- exact greedy token parity of every served request against an
  UNDISTURBED oracle (``serving.worker --oracle``, same spec/seed);
- the restarted worker re-warmed: ``spill_rewarm_chains > 0`` in its
  worker_ready line, and its goodbye stats show host-tier prefix hits
  (``hit_tokens_host > 0`` or ``promotes > 0``);
- bounded recovery: death detection -> replacement serving within
  ``$DDL_CHAOS_RECOVERY_S`` (wall; boot dominates on the CPU sim).

A final ``exhaustion`` run sets ``max_worker_restarts=0``: the crashed
worker is given up (``worker_give_up``), the fleet DEGRADES to the
survivor, and the same accounting/parity pins hold — graceful
degradation, not a hung run.

Usage:  python tools/serve_chaos.py            # full matrix, ~minutes
        python tools/serve_chaos.py --check    # re-validate committed
                                               # artifact, no processes

Shrink knobs (the tier-1 smoke leg, tests/test_serve_chaos.py):
$DDL_CHAOS_KINDS (comma list, default all four), $DDL_CHAOS_WAVE_A /
$DDL_CHAOS_WAVE_B (requests per wave), $DDL_CHAOS_FAULT_STEP,
$DDL_CHAOS_OUT, $DDL_CHAOS_TIMEOUT (per-run wall budget),
$DDL_CHAOS_SKIP_EXHAUSTION=1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_OUT = os.environ.get(
    "DDL_CHAOS_OUT", os.path.join(_REPO, "SERVE_CHAOS_STATUS.json")
)
_KINDS = tuple(
    k for k in os.environ.get(
        "DDL_CHAOS_KINDS",
        "worker_crash,worker_hang,conn_drop,heartbeat_stall",
    ).split(",") if k.strip()
)
_WAVE_A = int(os.environ.get("DDL_CHAOS_WAVE_A", "14"))
_WAVE_B = int(os.environ.get("DDL_CHAOS_WAVE_B", "14"))
# Fault step default: late enough that the target worker has cycled its
# lanes at least once (evictions -> host spills -> a periodic
# checkpoint with ROOT-CONNECTED chains — leaf-first eviction spills
# chain tails before roots, and load_spill_store() only adopts chains
# whose root survived to the file), early enough that wave A work is
# still in flight — the retry path must have something to retry.
# Measured on this workload (share 7, pool 9): the first loadable chain
# lands at step ~9, the store holds ~4 chains at step 18, and the share
# runs ~35 steps.
_FAULT_STEP = int(os.environ.get("DDL_CHAOS_FAULT_STEP", "18"))
_TIMEOUT_S = float(os.environ.get("DDL_CHAOS_TIMEOUT", "300"))
_RECOVERY_S = float(os.environ.get("DDL_CHAOS_RECOVERY_S", "120"))
_SKIP_EXHAUSTION = os.environ.get("DDL_CHAOS_SKIP_EXHAUSTION", "") == "1"
_SEED = int(os.environ.get("DDL_CHAOS_SEED", "0"))
_FLEET = 2
_FAULT_TARGET = 0

# The workload: tiny gpt2, shared-prefix trace (7 system prompts x short
# suffixes), prefix cache + spill tier on, device pool constrained WELL
# below the cached-prefix working set (7 prefixes x 2 blocks = 14
# against 9) — publishing one finished prefix evicts another whole one,
# so the periodic spill checkpoint holds root-connected chains for the
# restarted worker to re-warm from. The prefix count is ODD on purpose:
# the waves cycle prefixes round-robin and dispatch is round_robin over
# 2 workers, so each worker sees a stride-2 sample of the cycle — with
# an odd cycle length that sample covers EVERY prefix (stride 2 is a
# generator mod 7), and wave B is guaranteed to revisit whichever
# chains the restarted worker re-warmed, whatever the cursor offset.
_MODEL_KW = dict(size="tiny", vocab_size=256, max_len=160)
_PREFIXES = 7
_PREFIX_LEN = 32           # 2 whole blocks -> cacheable
_SUFFIX_LEN = (2, 9)
_MAX_NEW = (8, 13)         # >= 8 lower-bounds steps-before-idle vs the
                           # fault step; lane turnover still quick
_CONSTRAIN_BLOCKS = 9
_SERVING_KW = dict(
    slots=4, block_size=16, hbm_budget_mb=8, max_seq_len=96,
    prompt_buckets=[16, 32, 64], prefix_cache=True, suffix_buckets=[8],
    spill_blocks=24, router_policy="round_robin",
    # Timeout 5s, not 1s: a freshly-restarted worker's first steps can
    # hit >1s XLA compiles (new batch compositions, cold process), and
    # the single-threaded worker cannot heartbeat mid-step — a 1s sweep
    # quarantines the healthy-but-compiling and cascades.
    heartbeat_interval_s=0.05, heartbeat_timeout_s=5.0,
    max_worker_restarts=2, restart_backoff_base_s=0.2,
    restart_backoff_max_s=1.0, spill_checkpoint_every_s=0.05,
    request_retry=True,
)
# Slow each engine step slightly so the fault step fires while wave A
# still has queued + in-flight work on the target — the retry path must
# have something real to retry.
_DWELL_S = float(os.environ.get("DDL_CHAOS_DWELL", "0.01"))


def _shared_prefixes():
    """The system prompts BOTH waves ride: wave B must revisit wave A's
    prefixes, or the restarted worker's re-warmed host tier would have
    nothing to hit."""
    import numpy as np

    rng = np.random.default_rng(_SEED)
    return [
        [int(t) for t in rng.integers(1, 256, _PREFIX_LEN)]
        for _ in range(_PREFIXES)
    ]


def _make_requests(prefixes, seed: int, n: int, id_base: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        slen = int(rng.integers(*_SUFFIX_LEN))
        suffix = [int(t) for t in rng.integers(1, 256, slen)]
        reqs.append({
            "request_id": id_base + i,
            "prompt": prefixes[i % _PREFIXES] + suffix,
            "max_new_tokens": int(rng.integers(*_MAX_NEW)),
        })
    return reqs


def _spec(fault: str, *, max_restarts: int | None = None) -> dict:
    serving = dict(_SERVING_KW)
    serving["fault_injection"] = fault
    if max_restarts is not None:
        serving["max_worker_restarts"] = max_restarts
    return {
        "model": {"name": "gpt2", "kwargs": dict(_MODEL_KW)},
        "serving": serving,
    }


def _oracle_tokens(requests) -> dict:
    """Greedy parity reference: the SAME requests, one undisturbed
    engine, same pinned subprocess environment as the workers. The
    fault keys are stripped — the oracle is the no-chaos control."""
    spec = _spec("")
    spec["serving"].pop("fault_injection")
    payload = json.dumps({"requests": requests})
    out = subprocess.run(
        [sys.executable, "-m",
         "distributeddeeplearning_tpu.serving.worker",
         "--oracle", "--spec-json", json.dumps(spec),
         "--seed", str(_SEED)],
        input=payload, capture_output=True, text=True, check=True,
        cwd=_REPO,
    )
    for line in out.stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("event") == "oracle_result":
            return {int(k): v for k, v in rec["results"].items()}
    raise RuntimeError("oracle printed no oracle_result")


def _run_one(kind: str, *, max_restarts: int | None = None,
             label: str | None = None) -> dict:
    from distributeddeeplearning_tpu.cli import read_worker_ready
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import (
        FleetSupervisor, Request, connect_fleet,
    )
    from distributeddeeplearning_tpu.serving.worker import ATTEMPT_ENV

    label = label or kind
    fault = f"{kind}:{_FAULT_STEP}"
    spec = _spec(fault, max_restarts=max_restarts)
    scfg = ServingConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in spec["serving"].items()
    })
    spill_dir = tempfile.mkdtemp(prefix=f"serve_chaos_{kind}_")
    prefixes = _shared_prefixes()
    wave_a = _make_requests(prefixes, _SEED + 2, _WAVE_A, 0)
    wave_b = _make_requests(prefixes, _SEED + 3, _WAVE_B, _WAVE_A)
    submitted = wave_a + wave_b

    procs = [None] * _FLEET
    spawn_log = []

    def _spawn(index, attempt):
        cmd = [
            sys.executable, "-m",
            "distributeddeeplearning_tpu.serving.worker",
            "--spec-json", json.dumps(spec), "--seed", str(_SEED),
            "--replica-index", str(index),
            "--spill-store",
            os.path.join(spill_dir, f"spill_w{index}.json"),
            "--constrain-pool", str(_CONSTRAIN_BLOCKS),
            "--dwell-s", str(_DWELL_S),
        ]
        env = dict(os.environ)
        env["DDL_PROCESS_INDEX"] = str(index)
        env[ATTEMPT_ENV] = str(attempt)
        env["DDL_SERVE_FAULT_WORKER"] = str(_FAULT_TARGET)
        p = subprocess.Popen(
            cmd, env=env, cwd=_REPO, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True,
        )
        procs[index] = p
        ready = read_worker_ready(p.stdout)
        spawn_log.append({
            "replica": index, "attempt": attempt,
            "spill_rewarm_chains": int(
                ready.get("spill_rewarm_chains", 0)
            ),
        })
        return p, ready

    endpoints = []
    for i in range(_FLEET):
        _, ready = _spawn(i, 0)
        endpoints.append((ready["host"], ready["port"]))
    router = connect_fleet(scfg, endpoints)
    sup = FleetSupervisor(router, list(procs), _spawn, scfg)
    # Wall budget covers SERVING, not the AOT compiles of the initial
    # boot — on a CPU host the two serial worker boots alone can eat a
    # large fraction of it.
    t_run0 = time.monotonic()

    def _drive(until=None) -> bool:
        """Step router + supervisor until ``until()`` (or completion);
        False = the per-run wall budget ran out."""
        deadline = t_run0 + _TIMEOUT_S
        grace_s = scfg.heartbeat_timeout_s + 3.0
        t_drained = None
        while time.monotonic() < deadline:
            busy = router.step()
            sup.tick()
            if until is not None and until():
                return True
            if (not busy and not sup.pending_recovery and router.idle):
                if until is None:
                    return True
                # Fully drained with ``until`` still pending. Detection
                # can be wall-clock-driven with no work left to trigger
                # it — a stalled-heartbeat worker finishes its share
                # and only the stale sweep (heartbeat_timeout_s of
                # listened silence) outs it — so grant a grace window
                # before concluding the event can never fire.
                now = time.monotonic()
                if t_drained is None:
                    t_drained = now
                elif now - t_drained > grace_s:
                    return False
            else:
                t_drained = None
            if not busy:
                time.sleep(0.005)
        return False

    result: dict = {"run": label, "fault": fault,
                    "fleet": _FLEET, "fault_worker": _FAULT_TARGET}
    try:
        for d in wave_a:
            router.submit(Request(
                prompt=list(d["prompt"]),
                max_new_tokens=d["max_new_tokens"],
                request_id=d["request_id"],
            ))
        if max_restarts == 0:
            healed = _drive(until=lambda: sup.handles[
                _FAULT_TARGET].gave_up)
        else:
            healed = _drive(until=lambda: sup.restarts >= 1)
        # Wave B lands AFTER recovery (or give-up): the restarted worker
        # serves warm-prefix load; in the exhaustion run the survivor
        # absorbs everything.
        for d in wave_b:
            router.submit(Request(
                prompt=list(d["prompt"]),
                max_new_tokens=d["max_new_tokens"],
                request_id=d["request_id"],
            ))
        done = _drive()
        finished = router.finished()
        stats = router.stats()
        goodbye_stats = {}
        sup.shutdown()
        for r in router.replicas:
            gb = getattr(r, "goodbye", None) or {}
            goodbye_stats[r.index] = gb.get("stats") or {}
    finally:
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()

    served_ids = sorted(
        int(s.request.request_id) for s in finished
    )
    oracle = _oracle_tokens(submitted)
    parity = all(
        list(s.generated) == oracle[int(s.request.request_id)]
        for s in finished
    )
    sup_stats = sup.stats()
    restarted = sup_stats["restart_records"]
    target_goodbye = goodbye_stats.get(_FAULT_TARGET) or {}
    px = target_goodbye.get("prefix_cache") or {}
    rewarm_hits = int(px.get("hit_tokens_host") or 0)
    rewarm_promotes = int(px.get("promotes") or 0)
    rewarm_chains = max(
        (r["spill_rewarm_chains"] for r in restarted), default=0
    )
    served = len(finished)
    shed = int(stats.get("shed", 0))
    dropped = int(stats.get("failed", 0))
    exhaustion = max_restarts == 0

    checks = {
        "healed_or_gave_up": bool(healed),
        "completed": bool(done),
        "accounting_exact": served + shed + dropped == len(submitted),
        "no_duplicates": int(stats.get("duplicate_deliveries", 0)) == 0,
        "token_parity": bool(parity),
    }
    if exhaustion:
        checks["gave_up"] = sup_stats["gave_up"] == [_FAULT_TARGET]
        checks["survivor_served_all"] = dropped == 0 and served == len(
            submitted
        )
    else:
        checks["restarted"] = len(restarted) >= 1
        checks["nothing_dropped"] = dropped == 0
        checks["spill_rewarm"] = rewarm_chains > 0
        checks["rewarm_served_warm"] = (
            rewarm_hits > 0 or rewarm_promotes > 0
        )
        checks["recovery_bounded"] = all(
            r["recovery_s"] <= _RECOVERY_S for r in restarted
        )
    result.update({
        "submitted": len(submitted),
        "served": served,
        "shed": shed,
        "dropped": dropped,
        "served_ids": served_ids,
        "retried": int(stats.get("retried", 0)),
        "rerouted": int(stats.get("rerouted", 0)),
        "duplicate_deliveries": int(
            stats.get("duplicate_deliveries", 0)
        ),
        "stale_frames": int(stats.get("stale_frames", 0)),
        "stale_heartbeats": int(stats.get("stale_heartbeats", 0)),
        "token_parity": bool(parity),
        "restart_records": restarted,
        "supervisor": sup_stats,
        "spawns": spawn_log,
        "rewarm_hit_tokens_host": rewarm_hits,
        "rewarm_promotes": rewarm_promotes,
        # Merged lifecycle timeline (both streams stamp the router's
        # tick counter): what died, what was retried where, and WHY a
        # replica was quarantined (the error string carries the
        # measured heartbeat age) — the post-mortem for any red run.
        "events": sorted(
            list(router.events) + list(sup.events),
            key=lambda e: e.get("step", 0),
        ),
        "wall_s": round(time.monotonic() - t_run0, 3),
        "checks": checks,
        "ok": all(checks.values()),
    })
    return result


def check_status(status: dict) -> list[str]:
    """Validate an artifact against the pinned claims; the shared
    ``--check`` / post-run gate. Returns failure strings (empty = ok)."""
    fails = []
    runs = {r["run"]: r for r in status.get("runs", [])}
    for kind in status.get("kinds", []):
        r = runs.get(kind)
        if r is None:
            fails.append(f"{kind}: run missing")
            continue
        if not r.get("ok"):
            bad = [k for k, v in (r.get("checks") or {}).items()
                   if not v]
            fails.append(f"{kind}: failed checks {bad}")
        if r.get("served", -1) + r.get("shed", -1) + r.get(
                "dropped", -1) != r.get("submitted", 0):
            fails.append(f"{kind}: accounting broken")
        if r.get("duplicate_deliveries", 1) != 0:
            fails.append(f"{kind}: duplicate deliveries")
        if not r.get("token_parity"):
            fails.append(f"{kind}: token parity broken")
        if kind != "exhaustion":
            if not any(
                rec.get("spill_rewarm_chains", 0) > 0
                for rec in r.get("restart_records", [])
            ):
                fails.append(f"{kind}: no spill re-warm")
    if status.get("exhaustion_run") and "exhaustion" not in runs:
        fails.append("exhaustion: run missing")
    if not status.get("ok"):
        fails.append("status.ok is false")
    return fails


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--check" in argv:
        with open(_OUT) as f:
            status = json.load(f)
        fails = check_status(status)
        for f_ in fails:
            print(f"[serve-chaos-check] FAIL: {f_}")
        print(json.dumps({
            "check": "serve_chaos", "out": _OUT,
            "ok": not fails, "failures": fails,
        }))
        return 1 if fails else 0

    runs = []
    for kind in _KINDS:
        print(f"[serve-chaos] running {kind} ...", flush=True)
        runs.append(_run_one(kind))
        print(json.dumps({k: runs[-1][k] for k in
                          ("run", "ok", "served", "dropped", "retried",
                           "wall_s", "checks")}), flush=True)
    if not _SKIP_EXHAUSTION:
        print("[serve-chaos] running exhaustion ...", flush=True)
        runs.append(_run_one(
            "worker_crash", max_restarts=0, label="exhaustion",
        ))
        print(json.dumps({k: runs[-1][k] for k in
                          ("run", "ok", "served", "dropped",
                           "wall_s", "checks")}), flush=True)
    status = {
        "bench": "serve_chaos",
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "fleet": _FLEET,
        "kinds": list(_KINDS),
        "exhaustion_run": not _SKIP_EXHAUSTION,
        "fault_step": _FAULT_STEP,
        "seed": _SEED,
        "wave_a": _WAVE_A,
        "wave_b": _WAVE_B,
        "serving": dict(_SERVING_KW),
        "constrain_blocks": _CONSTRAIN_BLOCKS,
        "recovery_bound_s": _RECOVERY_S,
        "timebase": "wall-clock, XLA:CPU sim (mechanism pins only — "
                    "absolute latencies are not TPU predictions)",
        "runs": runs,
        "ok": all(r["ok"] for r in runs),
    }
    fails = check_status(status)
    status["check_failures"] = fails
    status["ok"] = status["ok"] and not fails
    with open(_OUT, "w") as f:
        json.dump(status, f, indent=1, sort_keys=False)
        f.write("\n")
    print(json.dumps({
        "bench": "serve_chaos", "out": _OUT, "ok": status["ok"],
        "runs": {r["run"]: r["ok"] for r in runs},
    }))
    return 0 if status["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
