"""M7 aux subsystems: fault injection + restart-based recovery, and the
2-process jax.distributed rendezvous (SURVEY §4 tier 3, §5).

The recovery model is restart-based: a crashed process is relaunched with
the same command and resumes from the last durable orbax checkpoint. The
fault-injection flag simulates the crash (os._exit, no cleanup) so the
whole flow is testable without a cluster.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from distributeddeeplearning_tpu.train import FaultSpec, parse_fault_injection

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_fault_injection():
    assert parse_fault_injection("") is None
    assert parse_fault_injection("step:5") == FaultSpec("step", 5)
    assert parse_fault_injection("nan:3") == FaultSpec("nan", 3)
    assert parse_fault_injection("hang:7") == FaultSpec("hang", 7)
    assert parse_fault_injection("corrupt:6") == FaultSpec("corrupt", 6)
    with pytest.raises(ValueError):
        parse_fault_injection("epoch:2")
    with pytest.raises(ValueError):
        parse_fault_injection("nan:x")


def _train_cmd(tmp_path, extra):
    return [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli", "train",
        "--config", os.path.join(REPO, "configs", "resnet18_cifar10.py"),
        "--override", "train.steps=8",
        "--override", "train.log_every=1",
        "--override", "train.save_every=2",
        "--override", f"train.checkpoint_dir={tmp_path}/ckpt",
        "--override", "data.batch_size=8",
        "--override", "data.image_size=8",
        "--override", 'model.kwargs={"num_classes":10,"width":8,"stem":"cifar"}',
        *extra,
    ]


def test_crash_and_resume(tmp_path):
    """Kill at step 5 via fault injection; relaunch resumes and finishes."""
    env = dict(os.environ)  # conftest already pinned CPU sim vars
    crashed = subprocess.run(
        _train_cmd(tmp_path, ["--override", "train.fault_injection=step:5"]),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert crashed.returncode == 17, crashed.stderr[-2000:]
    # The kill is announced through the metrics event stream, not a bare
    # print: one ordered stdout for supervisors to parse.
    assert '"event": "fault_kill"' in crashed.stdout
    # Steps 1..5 ran; a durable checkpoint exists at step 2 or 4.
    resumed = subprocess.run(
        _train_cmd(tmp_path, []),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "resumed from step" in resumed.stdout
    assert '"step": 8' in resumed.stdout  # trained through to the end


def _gpt2_file_cmd(tmp_path, token_path, extra):
    return [
        sys.executable, "-m", "distributeddeeplearning_tpu.cli", "train",
        "--config", os.path.join(REPO, "configs", "gpt2_owt.py"),
        "--override", 'model.kwargs={"size":"tiny","vocab_size":256,"max_len":64}',
        "--override", "data.kind=token_file_lm",
        "--override", f"data.path={token_path}",
        "--override", "data.batch_size=8",
        "--override", "data.seq_len=32",
        "--override", "optim.warmup_steps=0",
        "--override", "train.steps=8",
        "--override", "train.log_every=1",
        "--override", "train.save_every=2",
        "--override", f"train.checkpoint_dir={tmp_path}/ckpt",
        *extra,
    ]


def test_crash_and_resume_file_backed(tmp_path):
    """Step-exact resume on the REAL-DATA path: train GPT-2 from an on-disk
    token file, crash at step 5, relaunch — the resumed run's final losses
    must match an uninterrupted run exactly (same data order, same state)."""
    from distributeddeeplearning_tpu.data_text import write_token_file

    token_path = str(tmp_path / "corpus.tok")
    rng = np.random.default_rng(0)
    write_token_file(token_path, rng.integers(0, 250, 16385, np.int64), 256)
    env = dict(os.environ)

    def losses_of(run):
        import json

        out = {}
        for line in run.stdout.splitlines():
            if line.startswith("{") and '"loss"' in line:
                m = json.loads(line)
                out[m["step"]] = m["loss"]
        return out

    uninterrupted = subprocess.run(
        _gpt2_file_cmd(tmp_path / "a", token_path, []),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert uninterrupted.returncode == 0, uninterrupted.stderr[-2000:]

    crashed = subprocess.run(
        _gpt2_file_cmd(
            tmp_path / "b", token_path,
            ["--override", "train.fault_injection=step:5"],
        ),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert crashed.returncode == 17, crashed.stderr[-2000:]
    resumed = subprocess.run(
        _gpt2_file_cmd(tmp_path / "b", token_path, []),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "resumed from step 4" in resumed.stdout

    want = losses_of(uninterrupted)
    got = losses_of(resumed)
    assert set(got) == {5, 6, 7, 8}  # resumed at step 4, trained 5..8
    for step, loss in got.items():
        np.testing.assert_allclose(loss, want[step], rtol=1e-5, err_msg=str(step))


def _own_cache_env(tmp_path) -> dict:
    """A compile cache of this test's own, placed from outside the way a
    deployment does: these children crash and hang on purpose, and where
    ``supervisor.clear_cache_on_crash`` is on the supervisor wipes the
    resolved directory — which must not be the suite's shared one."""
    return dict(os.environ, JAX_COMPILATION_CACHE_DIR=f"{tmp_path}/xla")


def _supervise_cmd(tmp_path, extra):
    """The _train_cmd run under ``cli supervise`` with fast-test supervisor
    knobs (tiny backoff, tight poll)."""
    cmd = _train_cmd(tmp_path, [
        "--override", "supervisor.backoff_base_s=0.1",
        "--override", "supervisor.poll_interval_s=0.1",
        *extra,
    ])
    cmd[cmd.index("train")] = "supervise"
    return cmd


@pytest.mark.slow
def test_supervised_corrupt_recovery(tmp_path):
    """corrupt:6 truncates the latest durable checkpoint and crashes; the
    supervisor restarts, and the resume path falls back to the newest
    EARLIER durable step — the run still reaches the final step unattended."""
    run = subprocess.run(
        _supervise_cmd(tmp_path, [
            "--override", "train.fault_injection=corrupt:6",
        ]),
        capture_output=True, text=True, env=_own_cache_env(tmp_path), cwd=REPO,
        timeout=540,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    assert '"event": "fault_corrupt"' in run.stdout
    assert '"event": "supervisor_restart"' in run.stdout
    assert '"event": "fault_disarmed"' in run.stdout  # attempt 1 never re-fires
    assert "falling back" in run.stderr  # checkpoint.restore fallback fired
    assert '"step": 8' in run.stdout  # trained through to the end


@pytest.mark.slow
def test_supervised_hang_recovery(tmp_path):
    """hang:7 stalls the step loop; the heartbeat goes stale, the supervisor
    SIGKILLs and restarts, and the resumed attempt finishes the run."""
    run = subprocess.run(
        _supervise_cmd(tmp_path, [
            "--override", "train.fault_injection=hang:7",
            # Must exceed the first attempt's cold compile (the loop can't
            # touch the heartbeat while jit blocks the host).
            "--override", "supervisor.hang_timeout_s=120",
            # Off by default (the cache is shared); on here, with a cache
            # of the run's own: the clear must hit that resolved directory.
            "--override", "supervisor.clear_cache_on_crash=True",
        ]),
        capture_output=True, text=True, env=_own_cache_env(tmp_path), cwd=REPO,
        timeout=540,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    assert '"event": "fault_hang"' in run.stdout
    assert '"event": "supervisor_hang_kill"' in run.stdout
    assert (
        f'"event": "supervisor_cache_clear", "after": "hang", '
        f'"path": "{tmp_path}/xla"'
    ) in run.stdout
    assert '"step": 8' in run.stdout


@pytest.mark.slow
def test_supervised_nan_skip(tmp_path):
    """nan:5 poisons one step's gradients ON DEVICE; the health guard skips
    that update in-place — no crash, no restart, run completes with exactly
    one recorded anomaly."""
    run = subprocess.run(
        _supervise_cmd(tmp_path, [
            "--override", "train.fault_injection=nan:5",
            "--override", "health.enabled=True",
        ]),
        capture_output=True, text=True, env=_own_cache_env(tmp_path), cwd=REPO,
        timeout=540,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    assert '"skipped": 1.0' in run.stdout  # the poisoned step was skipped
    assert '"event": "supervisor_restart"' not in run.stdout
    assert '"step": 8' in run.stdout
    # Post-fault losses stay finite: the skip really protected the params.
    import json as json_lib

    losses = [
        json_lib.loads(line)["loss"]
        for line in run.stdout.splitlines()
        if line.startswith("{") and '"loss"' in line
    ]
    assert len(losses) == 8 and all(np.isfinite(losses))


@pytest.mark.slow
def test_sigterm_preemption_save_and_resume(tmp_path):
    """SIGTERM mid-run force-saves synchronously (off the save cadence),
    exits EXIT_PREEMPTED, and a relaunch resumes from exactly the preempted
    step — zero durable steps lost."""
    import json as json_lib
    import signal as signal_lib

    from distributeddeeplearning_tpu.supervisor import EXIT_PREEMPTED

    env = _own_cache_env(tmp_path)  # the relaunch warm-starts from it
    err_path = tmp_path / "preempt.err"
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            _train_cmd(tmp_path, [
                "--override", "train.steps=2000",
                "--override", "train.save_every=500",
            ]),
            stdout=subprocess.PIPE, stderr=err_f, text=True, env=env,
            cwd=REPO,
        )
        try:
            for line in proc.stdout:  # wait until training actually steps
                if '"loss"' in line:
                    break
            else:
                pytest.fail(f"no training line: {err_path.read_text()[-3000:]}")
            proc.send_signal(signal_lib.SIGTERM)
            rest, _ = proc.communicate(timeout=300)
        finally:
            proc.kill()
    assert proc.returncode == EXIT_PREEMPTED, err_path.read_text()[-3000:]
    ev = next(
        json_lib.loads(line) for line in rest.splitlines()
        if '"event": "preempt_save"' in line
    )
    assert ev["saved"] is True
    n = ev["step"]
    assert n >= 1 and n % 500 != 0  # off-cadence: the FORCE save path

    resumed = subprocess.run(
        _train_cmd(tmp_path, [
            "--override", f"train.steps={n + 2}",
        ]),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
    )
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    assert f"resumed from step {n}" in resumed.stdout
    assert f'"step": {n + 2}' in resumed.stdout


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# ONE hyperparameter set consumed by both the 2-process worker template
# and the in-process single-process oracle — copy drift between them would
# masquerade as a multi-host parity regression.
_MH = dict(vocab=128, max_len=64, seq=32, batch=8, lr=1e-3, steps=2)


def _mh_train_losses(mesh):
    """The training body both topologies run (same seeds, same data)."""
    from distributeddeeplearning_tpu import models
    from distributeddeeplearning_tpu.data import (
        SyntheticTokens,
        sharded_batches,
    )
    from distributeddeeplearning_tpu.train import (
        Trainer,
        get_task,
        make_optimizer,
    )

    model = models.get_model(
        "gpt2", size="tiny", vocab_size=_MH["vocab"], max_len=_MH["max_len"]
    )
    trainer = Trainer(
        model, make_optimizer("adamw", _MH["lr"]), get_task("lm"), mesh,
        donate=False,
    )
    ds = SyntheticTokens(
        batch_size=_MH["batch"], seq_len=_MH["seq"], vocab_size=_MH["vocab"]
    )
    state = trainer.init(0, ds.batch(0))
    losses = []
    for i, batch in enumerate(sharded_batches(ds.iter_from(0), mesh)):
        if i >= _MH["steps"]:
            break
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


_WORKER = """
import sys
import jax
from distributeddeeplearning_tpu.mesh import MeshConfig, build_mesh, init_distributed

addr, pid = sys.argv[1], int(sys.argv[2])
assert init_distributed(addr, 2, pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()

sys.path.insert(0, "tests")
import test_fault_tolerance as tft

losses = tft._mh_train_losses(build_mesh(MeshConfig(dp=8)))
print("LOSSES", losses)
"""


def test_two_process_rendezvous():
    """2-process jax.distributed over localhost: the multi-host init path,
    global mesh construction, and the make_array_from_process_local_data
    branch of sharded_batches — without a cluster."""
    port = _free_port()
    addr = f"localhost:{port}"
    from distributeddeeplearning_tpu.utils.compat import set_cpu_device_env

    env = dict(os.environ)
    set_cpu_device_env(env, 4)  # 2 procs x 4 = 8 global devices
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, addr, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=540) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-3000:]
    # Both processes computed the same global losses.
    lines = [
        next(line for line in out.splitlines() if line.startswith("LOSSES"))
        for out, _ in outs
    ]
    import ast

    l0 = ast.literal_eval(lines[0][len("LOSSES "):])
    l1 = ast.literal_eval(lines[1][len("LOSSES "):])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    assert all(np.isfinite(l0))
    # And the 2-process run must match the SINGLE-process dp=8 run on the
    # same seeds — per-host sharding is a placement detail, not math.
    # Both run _mh_train_losses: one definition, no copy drift.
    from helpers import mesh_of

    oracle = _mh_train_losses(mesh_of(dp=8))
    np.testing.assert_allclose(l0, oracle, rtol=1e-5)
