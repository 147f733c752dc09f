"""Unified telemetry (telemetry.py; docs/OBSERVABILITY.md): span tracer
ring/nesting/Chrome-trace validity, goodput ledger accounting on a fake
clock (categories sum to wall, replay classification across attempts),
device registry memory fields for a real compiled CPU-sim step, the
crash flight recorder's content after an injected NaN fault, heartbeat
content, serving gauges, and the TELEMETRY.json artifact contract.
"""

import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import HealthConfig, ServingConfig
from distributeddeeplearning_tpu.metrics import (
    DeferredMetrics,
    MetricWriter,
    event_record,
)
from distributeddeeplearning_tpu.supervisor import read_heartbeat, touch
from distributeddeeplearning_tpu.telemetry import (
    NULL_SPAN,
    NULL_TELEMETRY,
    DeviceRegistry,
    GoodputLedger,
    SpanTracer,
    Telemetry,
    dump_flight,
    memory_analysis_dict,
    read_goodput,
    record_backoff,
    resolve_dir,
    summarize_goodput,
    validate_chrome_trace,
)
from distributeddeeplearning_tpu.train import (
    HealthRollback,
    Trainer,
    fit,
    get_task,
    make_optimizer,
)

from helpers import mesh_of

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    """Advancable fake clock for ledger/tracer determinism."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s
        return self.t


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_args():
    clk = Clock()
    tr = SpanTracer(clock=clk)
    with tr.span("step", step=3):
        clk.advance(1.0)
        with tr.span("dispatch", step=3, k=2):
            clk.advance(2.0)
        clk.advance(0.5)
    # Inner span completes (and rings) first; depth counts enclosing spans.
    assert [s.name for s in tr.spans] == ["dispatch", "step"]
    dispatch, step = tr.spans
    assert dispatch.depth == 1 and step.depth == 0
    assert dispatch.args == {"step": 3, "k": 2}
    assert step.t_start < dispatch.t_start < dispatch.t_end < step.t_end


def test_span_ring_bounded_keeps_most_recent():
    tr = SpanTracer(ring_size=8, clock=Clock())
    for i in range(50):
        with tr.span("step", step=i):
            pass
    assert len(tr) == 8
    assert [s.args["step"] for s in tr.spans] == list(range(42, 50))


def test_disabled_tracer_and_null_telemetry_are_noops():
    tr = SpanTracer(enabled=False)
    cm = tr.span("step", step=0)
    assert cm is NULL_SPAN  # shared instance: zero allocation per span
    with cm:
        pass
    assert len(tr) == 0
    # The NULL bundle: every hook is inert, nothing touches disk.
    assert NULL_TELEMETRY.span("step") is NULL_SPAN
    assert NULL_TELEMETRY.ledger is None
    assert NULL_TELEMETRY.flight_dump("x") is None
    assert NULL_TELEMETRY.write_trace() is None
    assert NULL_TELEMETRY.trace_path is None
    NULL_TELEMETRY.note_event({"event": "x"})
    NULL_TELEMETRY.record_exe("x", None)
    assert len(NULL_TELEMETRY.registry) == 0


def test_timestamps_fenced_strictly_monotonic():
    # A stuck clock (coarse timer granularity) must still yield strictly
    # increasing timestamps — that fence is what makes the Chrome-trace
    # export well-formed by construction.
    tr = SpanTracer(clock=lambda: 5.0)
    for _ in range(4):
        with tr.span("step"):
            pass
    ts = [t for s in tr.spans for t in (s.t_start, s.t_end)]
    assert all(b > a for a, b in zip(ts, ts[1:]))


def test_chrome_trace_valid_and_json_roundtrip():
    clk = Clock()
    tr = SpanTracer(clock=clk)
    for i in range(5):
        with tr.span("step", step=i):
            clk.advance(0.001)
            with tr.span("dispatch", step=i):
                clk.advance(0.003)
            clk.advance(0.0005)
    trace = json.loads(json.dumps(tr.chrome_trace()))  # survives JSON
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    assert len(evs) == 20  # one B + one E per span
    assert sum(e["ph"] == "B" for e in evs) == sum(e["ph"] == "E" for e in evs)
    assert all(b["ts"] <= a["ts"] for b, a in zip(evs, evs[1:]))
    # args ride on the B event only.
    b0 = next(e for e in evs if e["ph"] == "B" and e["name"] == "dispatch")
    assert b0["args"]["step"] == 0


def test_chrome_trace_valid_after_ring_eviction():
    # Eviction drops oldest-COMPLETED spans: children ring before their
    # parents, so the surviving window is still properly nested.
    clk = Clock()
    tr = SpanTracer(ring_size=5, clock=clk)
    for i in range(20):
        with tr.span("step", step=i):
            clk.advance(0.001)
            with tr.span("dispatch", step=i):
                clk.advance(0.001)
    assert validate_chrome_trace(tr.chrome_trace()) == []


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace({"nope": 1}) == ["no traceEvents list"]
    bad_pair = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0},
        {"name": "b", "ph": "E", "ts": 1},
    ]}
    assert any("does not match" in p for p in validate_chrome_trace(bad_pair))
    unclosed = {"traceEvents": [{"name": "a", "ph": "B", "ts": 0}]}
    assert any("unclosed" in p for p in validate_chrome_trace(unclosed))
    backwards = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 5},
        {"name": "a", "ph": "E", "ts": 3},
    ]}
    assert any("<" in p for p in validate_chrome_trace(backwards))


def test_event_records_shape(tmp_path):
    clk = Clock()
    tr = SpanTracer(clock=clk)
    with tr.span("checkpoint", step=7, forced=True):
        clk.advance(0.25)
    (rec,) = tr.to_event_records()
    assert rec["event"] == "span" and rec["span"] == "checkpoint"
    assert rec["step"] == 7 and rec["forced"] is True
    assert rec["dur_ms"] == pytest.approx(250.0)
    path = tr.write_jsonl(str(tmp_path / "spans.jsonl"))
    with open(path) as f:
        assert json.loads(f.readline())["span"] == "checkpoint"


# ---------------------------------------------------------------------------
# goodput ledger (fake clock: exact accounting)
# ---------------------------------------------------------------------------


def test_goodput_two_attempts_replay_backoff_and_summary(tmp_path):
    path = str(tmp_path / "goodput.jsonl")
    clk = Clock()

    # Attempt 0: compile + 4 productive steps + a checkpoint stall.
    led0 = GoodputLedger(path, attempt=0, clock=clk)
    led0.open(0)
    clk.advance(1.0)
    led0.add("compile", 1.0)
    for i in range(4):
        clk.advance(0.5)
        led0.step_time(0.5, i + 1)
    clk.advance(0.3)
    led0.add("checkpoint_stall", 0.3)
    rec0 = led0.close(4)
    assert rec0["wall_s"] == pytest.approx(3.3)
    assert rec0["categories"]["productive_step"] == pytest.approx(2.0)
    assert rec0["categories"]["other"] == pytest.approx(0.0)
    assert sum(rec0["categories"].values()) == pytest.approx(rec0["wall_s"])
    assert rec0["steps_productive"] == 4 and rec0["steps_replayed"] == 0
    assert rec0["max_step"] == 4

    # The supervisor's backoff sleep before the restart.
    record_backoff(path, 1, 2.0)

    # Attempt 1 (new instance = new process) resumes from step 2: steps
    # 3..4 re-earn ground attempt 0 already covered -> rollback_replay.
    led1 = GoodputLedger(path, attempt=1, clock=clk)
    led1.open(2)
    for end in (3, 4, 5, 6):
        clk.advance(0.5)
        led1.step_time(0.5, end)
    rec1 = led1.close(6)
    assert rec1["steps_replayed"] == 2 and rec1["steps_productive"] == 2
    assert rec1["categories"]["rollback_replay"] == pytest.approx(1.0)

    s = summarize_goodput(path)
    assert s["attempts"] == 2
    assert s["wall_s"] == pytest.approx(3.3 + 2.0 + 2.0)
    assert s["categories"]["restart_backoff"] == pytest.approx(2.0)
    assert sum(s["categories"].values()) == pytest.approx(s["wall_s"])
    assert s["goodput_fraction"] == pytest.approx(3.0 / 7.3)
    assert s["steps_productive"] == 6 and s["steps_replayed"] == 2


def test_goodput_reader_skips_torn_trailing_line(tmp_path):
    path = str(tmp_path / "goodput.jsonl")
    led = GoodputLedger(path, clock=Clock())
    led.open(0)
    led.close(0)
    with open(path, "a") as f:
        f.write('{"record": "attempt", "wall_s": 1.0, "cat')  # crash mid-append
    assert len(read_goodput(path)) == 1  # torn line skipped, not fatal
    assert summarize_goodput(path) is None or True  # and never raises
    assert summarize_goodput(str(tmp_path / "absent.jsonl")) is None


# ---------------------------------------------------------------------------
# device registry + flight recorder + heartbeat (unit)
# ---------------------------------------------------------------------------


def test_device_registry_counts_recompiles():
    reg = DeviceRegistry()
    reg.record("train_step", None, compile_s=1.5, donated_args=2)
    assert "train_step" in reg and len(reg) == 1
    e = reg.get("train_step")
    assert e["compiles"] == 1 and e["recompiles"] == 0
    assert e["compile_s"] == pytest.approx(1.5)
    assert e["donated_args"] == 2 and e["memory_analysis"] is None
    reg.record("train_step", None, compile_s=1.0)
    assert e["recompiles"] == 1 and e["compile_s"] == pytest.approx(2.5)
    d = reg.to_dict()
    assert set(d["executables"]) == {"train_step"}


def test_dump_flight_truncates_and_carries_context(tmp_path):
    clk = Clock()
    tr = SpanTracer(clock=clk)
    for i in range(10):
        with tr.span("step", step=i):
            clk.advance(0.01)
    path = str(tmp_path / "flight_test.json")
    out = dump_flight(
        path, reason="fault_kill", tracer=tr,
        events=[{"event": "e", "step": i} for i in range(10)],
        last=4, step=9, phase="fault",
    )
    assert out == path
    with open(path) as f:
        rec = json.load(f)
    assert rec["reason"] == "fault_kill"
    assert rec["step"] == 9 and rec["phase"] == "fault"
    assert len(rec["spans"]) == 4 and len(rec["events"]) == 4
    assert rec["spans"][-1]["step"] == 9  # the LAST N, not the first


def test_heartbeat_content_roundtrip(tmp_path):
    p = str(tmp_path / "hb")
    touch(p, step=3, attempt=1, phase="save")
    assert read_heartbeat(p) == {"step": 3, "attempt": 1, "phase": "save"}
    legacy = str(tmp_path / "hb2")
    touch(legacy)  # mtime-only legacy form carries no content
    assert read_heartbeat(legacy) is None
    touch(None)  # no-op, never raises
    assert read_heartbeat(None) is None
    assert read_heartbeat(str(tmp_path / "missing")) is None


def test_resolve_dir_precedence(tmp_path):
    def cfg(tdir, ckpt):
        return types.SimpleNamespace(
            telemetry=types.SimpleNamespace(dir=tdir),
            train=types.SimpleNamespace(checkpoint_dir=ckpt),
        )

    assert resolve_dir(cfg("/x/tel", "/x/ckpt")) == "/x/tel"
    assert resolve_dir(cfg("", "/x/ckpt")) == "/x/ckpt/telemetry"
    assert resolve_dir(cfg("", "")).endswith("ddl_telemetry")


def test_deferred_metrics_flush_before_fault_event():
    # The fault branches exit via os._exit (no finally): the ONLY reason
    # the pending interval's metrics survive is emit_event's flush-first
    # contract — pinned here so the crash artifacts stay complete.
    history = []
    d = DeferredMetrics(history.append)
    d.push(2, {"loss": np.float32(1.5)})
    d.emit_event(event_record("fault_kill", 4))
    assert [h.get("event", "metrics") for h in history] == [
        "metrics", "fault_kill"
    ]
    assert history[0]["step"] == 2 and history[0]["loss"] == 1.5


def test_metric_writer_jsonl_lines(tmp_path):
    logdir = str(tmp_path / "tb")
    w = MetricWriter(logdir)
    w.write(1, {"loss": 2.5})
    w.write(2, {"loss": 1.25, "lr": 0.001})
    w.close()
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines == [
        {"schema": 1, "step": 1, "loss": 2.5},
        {"schema": 1, "step": 2, "loss": 1.25, "lr": 0.001},
    ]


# ---------------------------------------------------------------------------
# compiled CPU-sim: memory analysis + end-to-end fit
# ---------------------------------------------------------------------------


def test_memory_analysis_of_compiled_step():
    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.zeros((64, 64), jnp.float32)
    compiled = step.lower(x, x).compile()
    ma = memory_analysis_dict(compiled)
    assert ma is not None  # the CPU sim DOES report buffer accounting
    assert ma["argument_bytes"] == 2 * 64 * 64 * 4
    assert ma["output_bytes"] == 4
    assert all(isinstance(v, int) and v >= 0 for v in ma.values())


_SHARED: dict = {}


def _shared_trainer():
    """ONE guarded trainer (nan fault at step 2) for both e2e tests — a
    fresh Trainer costs a full jit compile; the clean-run test simply
    stops before the fault step (same trick as tests/test_health.py)."""
    if not _SHARED:
        mesh = mesh_of(dp=4)
        model = models.get_model(
            "gpt2", size="tiny", vocab_size=256, max_len=64, dropout_rate=0.0
        )
        _SHARED["mesh"] = mesh
        _SHARED["trainer"] = Trainer(
            model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
            donate=False, health=HealthConfig(enabled=True),
            fault_nan_step=2,
        )
    return _SHARED["mesh"], _SHARED["trainer"]


def _ds():
    return data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=256, seed=0, n_distinct=4
    )


def test_fit_e2e_writes_valid_artifacts(tmp_path):
    mesh, trainer = _shared_trainer()
    state = trainer.init(0, _ds().batch(0))
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    tel.ledger.open(0)
    fit(
        trainer, state, data_lib.sharded_batches(_ds().iter_from(0), mesh),
        steps=2, log_every=1, log_fn=lambda m: None, telemetry=tel,
    )
    rec = tel.ledger.close(2)
    tel.write_trace()

    # Registry: the first cold dispatch registered the executable (no AOT
    # double-compile) and the ledger classified it as compile time.
    e = tel.registry.get("train_step")
    assert e is not None and e["compiles"] == 1 and e["recompiles"] == 0
    assert e["compile_s"] > 0
    assert rec["categories"]["compile"] == pytest.approx(e["compile_s"])
    assert rec["steps_productive"] == 1  # step 2 of 2: the warm one

    # Ledger: categories sum to the measured wall within 1%.
    assert sum(rec["categories"].values()) == pytest.approx(
        rec["wall_s"], rel=0.01, abs=1e-4
    )

    # Trace: valid Chrome JSON on disk, with the standard loop spans.
    with open(tel.trace_path) as f:
        trace = json.load(f)
    assert validate_chrome_trace(trace) == []
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert {"step", "data_wait", "dispatch", "device_wait"} <= names
    # Fleet-stamped artifact names: process 0, this attempt.
    assert tel.spans_path.endswith("spans_p0_a0.jsonl")
    with open(tel.spans_path) as f:
        assert all(json.loads(ln)["event"] == "span" for ln in f)
    # The clock-alignment anchor was written eagerly at open.
    with open(tel.anchor_path) as f:
        anchor = json.load(f)
    assert anchor["record"] == "anchor" and anchor["process_index"] == 0


def test_fit_nan_rollback_dumps_flight_record(tmp_path):
    mesh, trainer = _shared_trainer()
    state = trainer.init(0, _ds().batch(0))
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    tel.ledger.open(0)
    with pytest.raises(HealthRollback) as ei:
        fit(
            trainer, state,
            data_lib.sharded_batches(_ds().iter_from(0), mesh),
            steps=8, log_every=1, log_fn=lambda m: None,
            health=HealthConfig(enabled=True, max_consecutive_anomalies=1),
            telemetry=tel,
        )
    tel.ledger.close()
    flight = os.path.join(tel.dir, "flight_health_rollback_p0_attempt0.json")
    assert os.path.exists(flight)
    with open(flight) as f:
        rec = json.load(f)
    assert rec["reason"] == "health_rollback"
    assert rec["phase"] == "rollback" and rec["attempt"] == 0
    assert rec["step"] == ei.value.step
    assert rec["spans"], "flight record carries the span ring"
    # The event mirror saw the same ordered stream fit emitted, ending in
    # the rollback event itself.
    assert rec["events"][-1]["event"] == "health_rollback"
    # write_trace ran on the unwind path too.
    with open(tel.trace_path) as f:
        assert validate_chrome_trace(json.load(f)) == []


# ---------------------------------------------------------------------------
# serving: gauges + per-executable registry
# ---------------------------------------------------------------------------


def test_serving_gauges_and_executable_registry(tmp_path):
    from distributeddeeplearning_tpu.serving import Request, ServingEngine

    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    cfg = ServingConfig(
        slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
        prompt_buckets=(8,), gauge_every=2,
    )
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    eng = ServingEngine(model, params, cfg, telemetry=tel)
    rng = np.random.default_rng(0)
    for n in (5, 7, 3):
        eng.submit(Request(
            prompt=list(map(int, rng.integers(1, 97, n))), max_new_tokens=6
        ))
    done = eng.run()
    assert len(done) == 3

    # Gauges: engine-level queue/pool occupancy at the configured cadence.
    gauges = [e for e in eng.events if e.get("event") == "serving_gauges"]
    assert gauges, "gauge_every=2 produced no gauge records"
    for g in gauges:
        assert g["step"] % 2 == 0
        for k in ("pending", "active", "free_blocks", "used_blocks"):
            assert isinstance(g[k], int) and g[k] >= 0

    # Registry: one entry per compiled program, zero recompiles (the
    # steady-state contract, now visible as data), with memory analysis.
    reg = tel.registry.to_dict()["executables"]
    assert "serving_decode" in reg and "serving_prefill_8" in reg
    for e in reg.values():
        assert e["recompiles"] == 0 and e["compile_s"] > 0
        assert e["memory_analysis"] is not None
        assert e["memory_analysis"]["argument_bytes"] > 0

    # Spans: the serving phases landed in the tracer ring; the event
    # mirror holds the same records run() emitted.
    names = {s.name for s in tel.tracer.spans}
    assert {"schedule", "prefill", "decode"} <= names
    assert validate_chrome_trace(tel.tracer.chrome_trace()) == []
    assert any(e.get("event") == "serving_gauges" for e in tel.events)


# ---------------------------------------------------------------------------
# CLI report + the committed TELEMETRY.json contract
# ---------------------------------------------------------------------------


def test_cmd_report_renders_dir(tmp_path, capsys):
    from distributeddeeplearning_tpu.cli import cmd_report

    tdir = str(tmp_path / "tel")
    tel = Telemetry(enabled=True, out_dir=tdir)
    tel.ledger.open(0)
    with tel.span("step", step=0):
        pass
    tel.ledger.close(0)
    tel.flight_dump("unit_test", step=0)
    tel.write_trace()
    assert cmd_report(tdir) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["goodput"]["attempts"] == 1
    # The merged trace carries the 2 span events plus this process's two
    # M (track-name) metadata events (and an X event should a long run of
    # the collector have fallen inside this tracer's short life).
    n_gc = sum(s.name == "gc" for s in tel.tracer.spans)
    assert out["trace"]["valid"] is True
    assert out["trace"]["events"] == 4 + n_gc
    assert out["flights"] == ["flight_unit_test_p0_attempt0.json"]
    assert out["processes"] == [0]
    assert out["headline"]["pod_goodput_fraction"] is not None
    # cmd_report is now the fleet aggregation pass: FLEET.json + the
    # merged trace land in the dir.
    assert os.path.exists(os.path.join(tdir, "FLEET.json"))
    assert os.path.exists(os.path.join(tdir, "trace_merged.json"))


def test_telemetry_artifact_check(tmp_path):
    # Import the tool in-process (its CPU-sim env preamble is inert under
    # the test harness: conftest already stripped the TPU pool var).
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_telemetry_report", os.path.join(_REPO, "tools",
                                          "telemetry_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    artifact = os.path.join(_REPO, "TELEMETRY.json")
    assert os.path.exists(artifact), "committed TELEMETRY.json missing"
    assert mod.check(artifact) == []

    # A tampered artifact must be rejected, not averaged away.
    with open(artifact) as f:
        art = json.load(f)
    art["overhead"]["overhead_fraction"] = 0.5
    bad = str(tmp_path / "TELEMETRY.json")
    with open(bad, "w") as f:
        json.dump(art, f)
    assert any("overhead" in p for p in mod.check(bad))
    assert mod.check(str(tmp_path / "absent.json"))  # unreadable -> problem
