"""What every cell's driver shares: finding the cell's files by name, the
device check, the compile cache, the compile log, memory, percentiles and
the one result line.

Nothing here belongs to one configuration, traffic mix or metric: those sit
in files of their own (``benchmarks/README.md``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


class BenchError(RuntimeError):
    """A run that must not print a result line."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark_json: str | None = None) -> Cell:
    """Find the cell ``name`` in BENCHMARK.json and read the configuration
    and traffic files that its ``config`` and ``traffic`` name."""
    spec = _load_json(benchmark_json or os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    reports = lambda m: "workloads" not in m or name in m["workloads"]  # noqa: E731
    return make_cell(
        name, w["chips"],
        os.path.join(REPO, files[w["config"]]),
        os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"),
        end_to_end=[m["name"] for m in spec["end_to_end"] if reports(m)],
        per_layer=[m for m in spec["per_layer"] if reports(m)],
    )


def make_cell(name, chips, config_path, traffic_path, *, end_to_end=None,
              per_layer=None) -> Cell:
    """A cell from explicit files (the tests build tiny ones this way)."""
    return Cell(
        name=name, chips=int(chips), config=_load_json(config_path),
        traffic=_load_json(traffic_path), end_to_end=end_to_end,
        per_layer=per_layer or [],
    )


def load_by_path(kind: str, name: str):
    """Import ``benchmarks/<kind>/<name>.py`` by file (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {path}")
    mod_name = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def require_program() -> None:
    """The system under test sits beside ``benchmarks/``; without it there
    is nothing to measure and no result is printed."""
    if not os.path.isdir(os.path.join(REPO, "distributeddeeplearning_tpu")):
        raise BenchError("the program is not beside benchmarks/")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


def device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def require_chips(chips: int) -> dict:
    """No CPU fallback: a TPU, and exactly the chips the cell asks for."""
    dev = device_record()
    if dev["platform"] != "tpu":
        raise BenchError(f"needs a TPU, found {dev}")
    if dev["count"] != chips:
        raise BenchError(f"cell needs {chips} chip(s), found {dev}")
    return dev


def setup_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed ``<checkout>/.jax_cache`` (the program's own entry points
    resolve the same directory). Every program is kept, however quickly it
    compiled: each run is a new process and pays for what is missing."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV) or os.path.join(REPO, ".jax_cache")
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Every program jax compiles or loads from its persistent cache, from
    jax's own monitoring events (copied from ``chip_smoke.CompileLog``). The
    event wraps ``compile_or_get_cached``: a cache hit fires it too, with the
    time the load took, so a program first met inside the window shows
    either way."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            self.events.append((kw.get("fun_name", "?"), float(duration)))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int):
        return self.events[mark:]

    def total(self, mark: int = 0) -> float:
        return sum(d for _, d in self.events[mark:])

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


class Phases:
    """Where set-up went: ``phases("name")`` notes the seconds since the
    process started; ``report`` writes them, and every backend compile of
    half a second or more, to standard error."""

    def __init__(self, t_process: float):
        self.t_process, self.marks = t_process, []

    def __call__(self, name: str) -> None:
        self.marks.append((name, time.perf_counter() - self.t_process))

    def report(self, compiles: CompileLog) -> None:
        sys.stderr.write("setup phases: " + ", ".join(
            f"{name} {t:.1f}" for name, t in self.marks
        ) + "\n")
        slow = [(n, round(d, 1)) for n, d in compiles.events if d >= 0.5]
        sys.stderr.write(f"backend compiles >= 0.5 s: {slow}\n")


def program_overrides(cell: Cell, **more) -> list[str]:
    """The configuration file's ``program.overrides``, each a template
    filled from the file's own top-level numbers, the traffic file's and
    ``more`` (``"model.kwargs.num_layers={n_layer}"``): the one place that
    maps a source's key names onto the program's, so that no driver knows a
    model family."""
    scalar = lambda d: {  # noqa: E731
        k: v for k, v in d.items() if isinstance(v, (int, float, str))
    }
    names = {**scalar(cell.config), **scalar(cell.traffic),
             "chips": cell.chips, **more}
    try:
        return [o.format(**names) for o in cell.config["program"]["overrides"]]
    except KeyError as e:
        raise BenchError(f"program.overrides names no {e} in {cell.name}")


def swap_in_reference_weights(state, ref, d: dict, seed: int):
    """Replace ``state.params`` by the seed's reference weights, made on
    the device in one jitted call straight into the program's layout and
    placement (the freshly initialised ones are deleted first)."""
    import jax

    make_params = jax.jit(
        lambda key: ref.program_tree(ref.weights_from_key(key, d), d),
        out_shardings=jax.tree.map(lambda x: x.sharding, state.params),
    )
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    key = ref.seed_key(seed)
    if shapes(state.params) != shapes(jax.eval_shape(make_params, key)):
        raise BenchError("the program's parameter tree is not the reference's")
    jax.tree.map(lambda x: x.delete(), state.params)
    return state.replace(params=make_params(key))


def span_telemetry():
    """The program's telemetry bundle with spans on and nothing else (no
    goodput file), for traced runs: ``fit`` and the engine record their
    spans into it. Its only file is a small anchor under ``.bench_out``."""
    from distributeddeeplearning_tpu.telemetry import Telemetry

    tel = Telemetry(
        enabled=True, out_dir=os.path.join(REPO, ".bench_out", "telemetry"),
        ring_size=1 << 20,
    )
    tel.ledger = None
    return tel


def spans_of(tel) -> list[tuple]:
    """(name, start, end, depth) on ``time.perf_counter``."""
    return [(s.name, s.t_start, s.t_end, s.depth) for s in tel.tracer.spans]


def window_steps(run: dict) -> list[tuple]:
    """The per-step counters a serving loop took inside the window."""
    t0, t1 = run.get("window", (0, 0))
    return [s for s in run.get("steps", ()) if t0 <= s[0] < t1]


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest local device."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return 0  # CPU rehearsal: the backend reports nothing
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all ``values`` (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise BenchError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN compares false: a run that gives no number is not correct.
        return bool(self.value <= self.limit)


def emit_result(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict, device: dict, breakdown: dict | None,
                extra: dict | None = None, out=None) -> bool:
    """The last lines of a run: each number compared beside its limit on
    standard error, then the one JSON line on standard output, with the
    comparisons under ``checked`` as its last key."""
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    for c in checks:
        sys.stderr.write(
            f"checked {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'NOT OK'}\n"
        )
    sys.stderr.flush()
    line = {
        "correct": correct,
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["checked"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in checks
    }
    (out or sys.stdout).write(json.dumps(line) + "\n")
    (out or sys.stdout).flush()
    return correct


def units_of(benchmark_json: str | None = None) -> dict:
    """{metric name: unit} as BENCHMARK.json states them."""
    spec = _load_json(benchmark_json or os.path.join(REPO, "BENCHMARK.json"))
    return {
        m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]
    }
