"""Span coverage of ``ServingEngine.step`` and the one-clock hook
(telemetry.py; docs/OBSERVABILITY.md): every ``engine_step`` span's children
nest as the span table says, ``schedule``/``prefill``/``decode`` start and
end where they always did, a run of the collector lands in the ring as a
``gc`` span one level below what was open, nothing at all happens with
telemetry off, and with ``annotate`` set the spans show, nested the same
way, on the host plane of a jax profile.
"""

import gc
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from distributeddeeplearning_tpu import models, telemetry
from distributeddeeplearning_tpu.config import ServingConfig
from distributeddeeplearning_tpu.serving import Request, ServingEngine
from distributeddeeplearning_tpu.telemetry import (
    NULL_TELEMETRY,
    SpanTracer,
    Telemetry,
    validate_chrome_trace,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TickClock:
    """Every read is one tick later than the last, so a span's start and
    end say how many clock reads (span edges) came before them: two edges
    with nothing between them differ by exactly one."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def tiny():
    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    return model, params


def _engine(tiny, tel=None, **cfg):
    model, params = tiny
    cfg = ServingConfig(
        slots=2, block_size=4, hbm_budget_mb=8, max_seq_len=48,
        prompt_buckets=(8,), gauge_every=2, **cfg,
    )
    return ServingEngine(model, params, cfg, telemetry=tel)


def _submit(eng, lengths=(5, 7, 3, 6), new=(4, 6, 3, 5)):
    rng = np.random.default_rng(0)
    for n, m in zip(lengths, new):
        eng.submit(Request(
            prompt=list(map(int, rng.integers(1, 97, n))), max_new_tokens=m
        ))


def _children(spans, parent):
    """Direct children of ``parent`` in start order."""
    return sorted(
        (s for s in spans
         if s.depth == parent.depth + 1
         and parent.t_start < s.t_start and s.t_end < parent.t_end),
        key=lambda s: s.t_start,
    )


def _assert_disjoint(children):
    for a, b in zip(children, children[1:]):
        assert a.t_end < b.t_start, (a, b)


@pytest.fixture
def no_collector():
    """The tick clock counts every read, the collector's too: keep it off
    but for the runs a test asks for itself."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def test_engine_step_children_tile_it_and_old_spans_keep_their_extents(
    tiny, tmp_path, no_collector
):
    clock = TickClock()
    tel = Telemetry(
        enabled=True, out_dir=str(tmp_path / "tel"), span_clock=clock
    )
    eng = _engine(tiny, tel)
    assert tel.tracer.annotate is jax.profiler.TraceAnnotation
    tel.tracer.annotate = None  # no profile is taken here
    eng.warmup()

    # Where the three old spans started and ended, by the tick at entry to
    # and exit from the one call each has always wrapped.
    edges = {"schedule": [], "prefill": [], "decode": []}

    def watched(name, fn):
        def wrapper(*a, **kw):
            t_in = clock.t
            out = fn(*a, **kw)
            edges[name].append((t_in, clock.t))
            return out
        return wrapper

    eng.scheduler.admit = watched("schedule", eng.scheduler.admit)
    eng._admit_one = watched("prefill", eng._admit_one)
    eng._decode_exe = watched("decode", eng._decode_exe_or_compile())
    _submit(eng)
    done = eng.run()
    assert len(done) == 4

    spans = tel.tracer.spans
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)

    # warmup > compile, one compile per executable, named.
    (warm,) = by_name["warmup"]
    compiles = _children(spans, warm)
    assert [s.name for s in compiles] == ["compile"] * eng.num_compiles
    assert {s.args["name"] for s in compiles} == {
        "serving_decode", "serving_prefill_8"
    }
    _assert_disjoint(compiles)

    # One engine_step per call of step(), top level, in step order.
    steps = by_name["engine_step"]
    assert [s.args["step"] for s in steps] == list(
        range(1, eng.step_count + 1)
    )
    assert all(s.depth == 0 for s in steps)
    n_prefill = n_decode = 0
    for st in steps:
        kids = _children(spans, st)
        _assert_disjoint(kids)
        names = [s.name for s in kids]
        k = names.count("prefill")
        tail = names[1 + k:]
        assert names[:1 + k] == ["schedule"] + ["prefill"] * k, names
        assert tail in ([], ["decode_prepare", "decode", "collect"]), names
        n_prefill += k
        n_decode += len(tail) // 3
        for p in (s for s in kids if s.name == "prefill"):
            inner = _children(spans, p)
            assert [s.name for s in inner] == [
                "prefill_prepare", "prefill_readback"
            ]
            # the first and the last line of _admit_one
            assert inner[0].t_start == p.t_start + 1
            assert inner[1].t_end == p.t_end - 1
    assert n_prefill == eng.calls["prefill"] == len(by_name["prefill"]) == 4
    assert n_decode == eng.calls["decode"] == len(by_name["decode"])
    assert "draft" not in by_name  # speculation is off

    # schedule, prefill and decode open one tick before the call they wrap
    # and close one tick after it returns, as on the parent: no span edge
    # lies between the span's and the call's.
    for name in ("schedule", "prefill", "decode"):
        got = [(s.t_start, s.t_end) for s in by_name[name]]
        want = [(t_in, t_out + 1) for t_in, t_out in edges[name]]
        assert got == want, name

    # The new names feed histograms like the old ones (cli report).
    for name in ("engine_step", "decode_prepare", "collect",
                 "prefill_prepare", "prefill_readback", "warmup", "compile"):
        assert tel.hists[name].count == len(by_name[name])

    # A run of the collector while spans are open: one gc span, one level
    # below the innermost, and the Chrome trace still validates.
    with tel.span("engine_step", step=0):
        with tel.span("decode", step=0):
            gc.collect()
    (g,) = [s for s in tel.tracer.spans if s.name == "gc"]
    assert g.depth == 2 and g.args["generation"] == 2
    assert tel.hists["gc"].count == 1
    trace = tel.tracer.chrome_trace()
    assert validate_chrome_trace(trace) == []
    (x,) = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert x["name"] == "gc" and x["dur"] >= 1


def test_speculative_step_opens_draft_and_keeps_the_tiling(tiny, tmp_path):
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"))
    eng = _engine(tiny, tel, speculation="ngram:2")
    eng.warmup()
    rng = np.random.default_rng(1)
    base = list(map(int, rng.integers(1, 97, 4)))
    eng.submit(Request(prompt=base + base, max_new_tokens=8))
    eng.run()
    spans = tel.tracer.spans
    seen = set()
    for st in (s for s in spans if s.name == "engine_step"):
        kids = [s for s in _children(spans, st) if s.name != "gc"]
        _assert_disjoint(kids)
        names = [s.name for s in kids if s.name != "prefill"]
        assert names in (
            ["schedule"],
            ["schedule", "draft", "decode_prepare", "decode", "collect"],
        ), names
        seen.update(names)
    assert "draft" in seen
    n_decode = sum(s.name == "decode" for s in spans)
    assert n_decode == eng.calls["decode"] + eng.calls["verify"]
    assert validate_chrome_trace(tel.tracer.chrome_trace()) == []


def test_null_telemetry_installs_and_records_nothing(tiny):
    callbacks = list(gc.callbacks)
    eng = _engine(tiny)
    eng.warmup()
    _submit(eng)
    assert len(eng.run()) == 4
    assert gc.callbacks == callbacks
    assert NULL_TELEMETRY.tracer.annotate is None
    assert all(
        ref() is not NULL_TELEMETRY.tracer for ref in telemetry._gc_watchers
    )
    assert len(NULL_TELEMETRY.tracer) == 0 and not NULL_TELEMETRY.hists

    # In a process of its own: importing the module and making disabled
    # bundles leaves the collector alone; the first enabled bundle installs
    # the one hook, a second adds no other.
    code = (
        "import gc, sys, tempfile\n"
        "n = len(gc.callbacks)\n"
        "from distributeddeeplearning_tpu import telemetry as t\n"
        "t.Telemetry(enabled=False, out_dir=None)\n"
        "t.Telemetry(enabled=True, out_dir=None)\n"
        "assert len(gc.callbacks) == n, gc.callbacks\n"
        "assert 'jax' not in sys.modules\n"
        "d = tempfile.mkdtemp()\n"
        "a = t.Telemetry(enabled=True, out_dir=d)\n"
        "b = t.Telemetry(enabled=True, out_dir=d)\n"
        "assert gc.callbacks[n:] == [t._on_gc], gc.callbacks\n"
        "assert len(t._gc_watchers) == 2\n"
        "del a, b\n"
        "gc.collect()\n"
        "assert len(t._gc_watchers) == 0\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_annotated_spans_nest_the_same_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    tracer = SpanTracer()
    tracer.annotate = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("engine_step", step=1):
            with tracer.span("prefill"):
                with tracer.span("prefill_prepare"):
                    jax.numpy.ones(4).block_until_ready()
            with tracer.span("decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    names = {s.name for s in tracer.spans}
    on_plane = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    on_plane[ev.name] = (
                        ev.start_ns, ev.start_ns + ev.duration_ns
                    )
    assert set(on_plane) == names

    def holds(outer, inner, of):
        return of[outer][0] <= of[inner][0] and of[inner][1] <= of[outer][1]

    in_ring = {s.name: (s.t_start, s.t_end) for s in tracer.spans}
    pairs = [(a, b) for a in names for b in names if a != b]
    assert [holds(a, b, on_plane) for a, b in pairs] == [
        holds(a, b, in_ring) for a, b in pairs
    ]
    assert holds("engine_step", "prefill", on_plane)
    assert holds("prefill", "prefill_prepare", on_plane)
    assert not holds("prefill", "decode", on_plane)
