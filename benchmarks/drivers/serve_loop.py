"""Driver ``serve_loop``: a serving cell, timed through
``ServingEngine.submit`` and ``.step`` on the harness's clock, the engine
built as ``cli.cmd_serve`` builds it (``cli.build_all``,
``cli.serving_model_and_state``, ``engine.warmup``), with token-id prompts
at the published vocabulary.

The loop is the shape of ``tools/serve_bench.py``'s: submit what is due,
step, collect. A closed loop submits a client's next request when its last
one finishes (due then); an open loop submits on the schedule, and a request
is timed from when it was due, not from when the loop got round to it.

After the window closes the loop goes on, with no new requests, until every
request that was due in the window has its first token. Then the engine is
freed and the plain reference runs over a sample of the finished requests.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time

import numpy as np

from benchmarks.harness import correct, traffic as traffic_lib
from benchmarks.harness import trace as trace_lib
from benchmarks.harness.common import (
    REPO,
    BenchError,
    Cell,
    CompileLog,
    load_by_path,
    Phases,
    memory_peak_bytes,
    program_overrides,
    span_telemetry,
    spans_of,
    swap_in_reference_weights,
)

DRAIN_LIMIT_S = 60.0


def program_config(cell: Cell, seed: int):
    from distributeddeeplearning_tpu.config import apply_overrides, load_config

    c = cell.config
    s = c["serving"]
    overrides = [
        f"train.seed={seed % (1 << 31)}",
        *[f"serving.{k}={tuple(v) if isinstance(v, list) else v}"
          for k, v in s.items()],
        *program_overrides(cell),  # attn_impl=xla, as `cli serve` asks
    ]
    return apply_overrides(
        load_config(os.path.join(REPO, c["program"]["config"])), overrides
    )


def build_engine(cell: Cell, seed: int, telemetry, ref, d):
    """``cmd_serve``'s build, with the seed's reference weights swapped in
    for the freshly initialised ones."""
    from distributeddeeplearning_tpu import cli
    from distributeddeeplearning_tpu.serving import (
        ServingEngine,
        check_serving_composition,
    )

    cfg = program_config(cell, seed)
    check_serving_composition(cfg)
    with contextlib.redirect_stdout(sys.stderr):  # "no checkpoint" line
        _mesh, model, trainer, dataset = cli.build_all(cfg)
        model, state = cli.serving_model_and_state(
            cfg, model, trainer, dataset
        )
    state = swap_in_reference_weights(state, ref, d, seed)
    engine = ServingEngine(
        model, state.params, cfg.serving, seed=cfg.train.seed,
        telemetry=telemetry, clock=time.perf_counter,
    )
    engine.warmup()
    return engine, state


class Loop:
    """Submit what is due, step, collect; one instance per run."""

    def __init__(self, engine, source, arrivals: dict, seed: int, sample):
        from distributeddeeplearning_tpu.serving import Request

        self._Request = Request
        self.engine, self.source, self.arrivals = engine, source, arrivals
        self.closed = arrivals["kind"] == "closed"
        self.clients = int(arrivals.get("clients", 0))
        self.sample = sample  # per-step counters on (traced runs)
        self.records: list[dict] = []  # one per request, submit order
        self.by_id: dict[int, dict] = {}
        self.next_index = 0
        self.free_clients: list[tuple[int, float]] = []  # (client, due)
        self.started: set[int] = set()
        self.seen_finished = 0
        self.steps: list[tuple] = []  # (t, active, used_share, live_tokens)
        self.walls: list[tuple] = []  # (t, seconds in pump, in engine.step)
        self.schedule: list[float] = []
        self.late_s: list[float] = []

    def start(self, t0: float, horizon_s: float, seed: int):
        if self.closed:
            self.free_clients = [(c, t0) for c in range(self.clients)]
        else:
            self.schedule = [
                t0 + t for t in
                traffic_lib.arrival_times(self.arrivals, seed, horizon_s)
            ]
            self.schedule.reverse()  # pop() the earliest

    def _submit(self, due: float, client: int | None, first: bool):
        req = self.source.request(self.next_index, first=first)
        self.next_index += 1
        state = self.engine.submit(
            self._Request(prompt=req.prompt, max_new_tokens=req.max_new_tokens),
            now=due,
        )
        rec = {"state": state, "due": due, "client": client,
               "index": req.index}
        self.records.append(rec)
        self.by_id[state.request.request_id] = rec

    def pump(self, now: float):
        """Submit every request that is due at ``now``."""
        if self.closed:
            for client, due in self.free_clients:
                first = client not in self.started
                self.started.add(client)
                self._submit(due, client, first)
            self.free_clients = []
        else:
            while self.schedule and self.schedule[-1] <= now:
                due = self.schedule.pop()
                self.late_s.append(now - due)
                self._submit(due, None, False)

    def step(self):
        eng = self.engine
        busy = eng.step()
        fin = eng.scheduler.finished
        for state in fin[self.seen_finished:]:
            rec = self.by_id[state.request.request_id]
            if rec["client"] is not None:
                self.free_clients.append((rec["client"], state.finish_s))
        self.seen_finished = len(fin)
        if self.sample:
            active = eng.scheduler.active
            pool = eng.scheduler.pool
            self.steps.append((
                time.perf_counter(), len(active),
                pool.used_blocks / max(1, pool.used_blocks + pool.free_blocks),
                sum(len(s.request.prompt) + len(s.generated) for s in active),
            ))
        return busy

    def run_until(self, t_end: float):
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self.pump(now)
            t_pumped = time.perf_counter()
            busy = self.step()
            self.walls.append(
                (now, t_pumped - now, time.perf_counter() - t_pumped)
            )
            if not busy and not self.free_clients:
                # idle engine: an open loop waits for its next arrival
                nxt = self.schedule[-1] if self.schedule else t_end
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, hooks: dict | None = None) -> dict:
    import jax

    hooks = hooks or {}
    ph = Phases(t_process)
    ph("imports")
    ref = load_by_path("references", cell.config["reference"])
    d = ref.dims(cell.config)
    t = cell.traffic
    compiles = CompileLog()
    tel = span_telemetry() if trace else None
    engine, state = build_engine(cell, seed, tel, ref, d)
    ph("build and warm up the engine")
    if "after_build" in hooks:
        hooks["after_build"](engine)
    source = traffic_lib.RequestSource(t, seed, d["vocab_size"])
    loop = Loop(engine, source, t["arrivals"], seed, sample=trace)

    # -- ramp: the traffic itself, before the window --------------------
    ramp_s = float(t.get("ramp_seconds", 0.0))
    t_ramp = time.perf_counter()
    loop.start(t_ramp, ramp_s + seconds, seed)
    loop.run_until(t_ramp + ramp_s)
    ph("ramp")
    compile_setup_s = compiles.total()
    mark = compiles.mark()

    # -- the window ------------------------------------------------------
    tw = None
    t_w0 = time.perf_counter()
    t_w1 = t_w0 + seconds
    if trace:
        trace_s = min(float(t.get("trace_seconds", 3.0)), seconds / 2)
        loop.run_until(t_w1 - trace_s)
        tw = trace_lib.TraceWindow(os.path.join(REPO, ".bench_out", "trace"))
        tw.start()
        loop.run_until(t_w1)
        tw.stop()
    else:
        loop.run_until(t_w1)
    t_w1 = time.perf_counter()
    window_s = t_w1 - t_w0

    # -- past the close: no new requests; wait for the first token of
    #    every request that was due in the window ----------------------
    in_window = [r for r in loop.records if t_w0 <= r["due"] < t_w1]
    deadline = t_w1 + DRAIN_LIMIT_S
    while any(r["state"].first_token_s is None for r in in_window):
        if time.perf_counter() > deadline or not loop.step():
            break
    window_compiles = compiles.since(mark)
    compiles.close()
    ph.report(compiles)
    if window_compiles:
        raise BenchError(f"compiled inside the window: {window_compiles}")
    peak = memory_peak_bytes()

    # -- what the window holds ------------------------------------------
    never = [r for r in in_window if r["state"].first_token_s is None]
    ttft = [
        r["state"].first_token_s - r["due"] for r in in_window
        if r["state"].first_token_s is not None
    ]
    gaps, tokens_in_window, decode_ctx, decode_tokens = [], 0, 0, 0
    prefill_lens = []
    for r in loop.records:
        st = r["state"]
        times = st.token_times_s
        plen = len(st.request.prompt)
        if st.admit_s is not None and t_w0 <= st.admit_s < t_w1:
            prefill_lens.append(plen)
        for j, tt in enumerate(times):
            if t_w0 <= tt < t_w1:
                tokens_in_window += 1
                if j > 0:
                    # token j came from a decode step over plen + j keys
                    decode_tokens += 1
                    decode_ctx += plen + j
                    if times[j - 1] >= t_w0:
                        gaps.append(tt - times[j - 1])
    finished = [
        r for r in loop.records
        if r["state"].done and not r["state"].dropped
        and t_w0 <= r["state"].finish_s < t_w1
    ]
    if not ttft or not gaps or not finished:
        raise BenchError("the window finished no request")

    record = {
        "cell": cell, "dims": d, "device_kind": jax.devices()[0].device_kind,
        "window_s": window_s, "chips": cell.chips,
        "compile_s": compile_setup_s, "slots": engine.slots_n,
        "prefill_lens": prefill_lens, "decode_tokens": decode_tokens,
        "decode_ctx": decode_ctx, "steps": loop.steps,
        "window": (t_w0, t_w1), "late_s": loop.late_s,
        "ttft_s": ttft, "token_gaps_s": gaps,
        "spans": [], "trace": None,
    }
    if trace:
        spans = record["spans"] = spans_of(tel)
        record["trace"] = tw.reduce(spans, keep=bool(hooks.get("keep_trace")))
        record["trace_window"] = (tw.t_start, tw.t_stop)

    # -- the sample for the reference, drawn from the seed --------------
    n_sample = int(t.get("check_requests", 12))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    longest = max(
        finished, key=lambda r: len(r["state"].request.prompt)
        + len(r["state"].generated),
    )
    picks = [longest] + [
        finished[i] for i in
        rng.permutation(len(finished))[:n_sample] if finished[i] is not longest
    ]
    served = [
        (list(r["state"].request.prompt), list(r["state"].generated))
        for r in picks
    ]
    attempted, failed = len(in_window), len(never)

    # -- free the engine, then the reference ----------------------------
    for r in loop.records:
        r["state"] = None
    jax.tree.map(lambda x: x.delete() if hasattr(x, "delete") else None,
                 (state, engine._cache))
    walls = loop.walls
    del engine, state, loop
    jax.clear_caches()
    t_ref = time.perf_counter()
    logit_gap, n_tokens = reference_gap(ref, seed, d, served)
    record["reference_s"] = time.perf_counter() - t_ref
    sys.stderr.write(
        f"reference {record['reference_s']:.1f} s; whole run "
        f"{time.perf_counter() - t_process:.1f} s\n"
    )
    if hooks.get("in_place") == "control":
        # harness/faults.py: the reference at the precision below the
        # stated one, in the program's place, on the same prompts and tokens
        logit_gap, _ = reference_gap(
            ref, seed, d, served, control=cell.config["control_precision"]
        )
    checks = correct.serving_checks(logit_gap, cell.config["limits"])
    # where a run's time went, should one read far off: its longest steps
    # (in a traced run with the engine's own spans inside each)
    def inside(t0, t1):
        out: dict[str, float] = {}
        for name, s0, s1, _depth in record["spans"]:
            if t0 <= s0 < t1:
                out[name] = out.get(name, 0.0) + (s1 - s0)
        return out

    in_w = sorted((w for w in walls if t_w0 <= w[0] < t_w1),
                  key=lambda w: -(w[1] + w[2]))
    extra = {"checked_tokens": n_tokens, "steps": {
        "n": len(in_w),
        "longest": [
            {"at_s": w[0] - t_w0, "pump_s": w[1], "step_s": w[2],
             "spans": inside(w[0], w[0] + w[1] + w[2])}
            for w in in_w[:3]
        ],
    }}

    metrics = {
        "setup_s": t_w0 - t_process,
        "serve_tokens_per_s": tokens_in_window / window_s,
    }
    return {
        "checks": checks, "attempted": attempted, "failed": failed,
        "end_to_end": metrics, "record": record, "memory_peak_bytes": peak,
        "extra": extra,
    }


def reference_gap(ref, seed: int, d: dict, served, precision: str = "f32",
                  control: str | None = None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``served`` ((prompt,
    generated) pairs), the reference run once over each prompt with its
    served tokens. With ``control`` (a lower precision), the token read at
    each position is the one that precision puts first, not the served one.
    """
    w = ref.make_weights(seed, d)
    worst, n = 0.0, 0
    for prompt, gen in served:
        seq = (prompt + gen)[: d["n_positions"]]
        inputs = seq[:-1]
        picks = seq[1:]
        if control is not None:
            _, amax, _ = ref.sequence_readout(
                w, inputs, picks, d, precision=control, pad_to=256
            )
            picks = [int(x) for x in amax]
        best, _, picked = ref.sequence_readout(
            w, inputs, picks, d, precision=precision, pad_to=256
        )
        lo = len(prompt) - 1  # position whose next token is generated[0]
        gap = best[lo:] - picked[lo:]
        if not np.all(np.isfinite(gap)):
            return float("nan"), n
        worst = max(worst, float(np.max(gap)))
        n += len(gap)
    return worst, n
