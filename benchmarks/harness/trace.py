"""Reduction of a jax profiler trace (``.xplane.pb``) to numbers: device
busy seconds, the operations that took most time, kernel time by name, and
the idle gaps by what the host was doing. Read with nothing but jax
(``jax.profiler.ProfileData``).

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` has one event per operation run on the device and whose line
``XLA Modules`` has one event per run of a compiled program; host threads
sit under ``/host:CPU``. Times are nanoseconds on one clock for all planes.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "bench_anchor"


CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class DeviceTrace:
    ops: list[tuple[str, float, float]]  # (short name, start_s, end_s)
    modules: list[tuple[str, float, float]]
    # short names of the ops that are Pallas (Mosaic) custom calls
    custom_calls: set = dataclasses.field(default_factory=set)


def short_name(text: str) -> str:
    """The trace names an op by its whole HLO line (``%attn.135 = (bf16[...
    custom-call(...)``): keep the instruction's name, ``attn.135``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def family(name: str) -> str:
    """``attn.135`` -> ``attn``: the ops of one kind, whatever their number."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclasses.dataclass
class Trace:
    devices: list[DeviceTrace]
    anchor_s: float | None  # trace-clock time of the anchor annotation


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, anchor = [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            grab = lambda name: [  # noqa: E731
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in lines[name].events
            ] if name in lines else []
            raw = grab(OPS_LINE)
            devices.append(DeviceTrace(
                [(short_name(n), s, e) for n, s, e in raw],
                grab(MODULES_LINE),
                {short_name(n) for n, _, _ in raw if CUSTOM_CALL in n},
            ))
        elif anchor is None:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns * 1e-9
                        break
                if anchor is not None:
                    break
    return Trace(devices, anchor)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(dev: DeviceTrace) -> float:
    return sum(b - a for a, b in union((s, e) for _, s, e in dev.ops))


def op_seconds(dev: DeviceTrace) -> dict[str, float]:
    """Self time per operation name. Events on the ops line nest (a while
    loop holds its body's ops), so an event's self time is its duration
    less what its children cover."""
    evs = sorted(dev.ops, key=lambda e: (e[1], -e[2]))
    total: dict[str, float] = {}
    stack: list[list] = []  # [name, end, child_seconds, start]

    def close(item):
        name, end, child, start = item
        total[name] = total.get(name, 0.0) + max(0.0, (end - start) - child)

    for name, s, e in evs:
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, s])
    while stack:
        close(stack.pop())
    return total


def seconds_matching(dev: DeviceTrace, needles,
                     custom_only: bool = False) -> tuple[float, int]:
    """Summed duration and count of the ops whose name holds any needle
    (outermost matches only, so a nested match is not counted twice);
    ``custom_only`` keeps Pallas custom calls alone."""
    hits = sorted(
        (s, e) for name, s, e in dev.ops
        if any(n in name for n in needles)
        and (not custom_only or name in dev.custom_calls)
    )
    total, count, last_end = 0.0, 0, -1.0
    for s, e in hits:
        if s >= last_end:
            total += e - s
            count += 1
            last_end = e
    return total, count


def module_runs(dev: DeviceTrace, needle: str) -> list[tuple[float, float]]:
    return [(s, e) for name, s, e in dev.modules if needle in name]


def idle_gaps(dev: DeviceTrace, t0: float, t1: float):
    """Gaps between device operations inside [t0, t1] (trace clock)."""
    gaps, cur = [], t0
    for a, b in union((s, e) for _, s, e in dev.ops):
        if a > cur:
            gaps.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(a, b) for a, b in gaps if b > a]


def attribute_gaps(gaps, spans, default: str = "host_other"):
    """Seconds of ``gaps`` by the innermost host span that covers each
    part. ``spans``: (name, start_s, end_s, depth) on the trace clock."""
    by_name: dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    for g0, g1 in gaps:
        covered = []  # (start, end, depth, name) clipped to the gap
        for name, s, e, depth in spans:
            if e <= g0:
                continue
            if s >= g1:
                break
            covered.append((max(s, g0), min(e, g1), depth, name))
        # cut the gap at every span edge; the deepest span wins each piece
        edges = sorted({g0, g1, *[c[0] for c in covered],
                        *[c[1] for c in covered]})
        for a, b in zip(edges, edges[1:]):
            mid = 0.5 * (a + b)
            inside = [c for c in covered if c[0] <= mid < c[1]]
            name = max(inside, key=lambda c: c[2])[3] if inside else default
            by_name[name] = by_name.get(name, 0.0) + (b - a)
    return by_name


def top(by_name: dict[str, float], n: int = 10):
    return [
        [k, v] for k, v in
        sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    ]


class TraceWindow:
    """Start and stop the profiler around a piece of the window, with an
    anchor annotation that ties ``time.perf_counter`` to the trace clock."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.t_start = self.t_stop = self.t_anchor = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.t_anchor = time.perf_counter()
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_start

    def reduce(self, host_spans=(), keep: bool = False) -> dict:
        """``host_spans``: (name, start, end, depth) on perf_counter."""
        trace = load(find_xplane(self.dir))
        if not keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduce_trace(
            trace, self.window_s, host_spans,
            anchor_perf=self.t_anchor, start_perf=self.t_start,
            stop_perf=self.t_stop,
        )


def idle_share(reduced: dict | None):
    """Percent of the traced seconds in which no operation ran on the chip,
    from what ``reduce_trace`` returns; None where there is no trace."""
    if not reduced or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce_trace(trace: Trace, window_s: float, host_spans=(), *,
                 anchor_perf=None, start_perf=None, stop_perf=None) -> dict:
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    busy = [busy_seconds(d) for d in trace.devices]
    dev0 = trace.devices[0]
    ops: dict[str, float] = {}
    for name, sec in op_seconds(dev0).items():
        fam = family(name)
        if name in dev0.custom_calls:
            fam += "(pallas)"
        ops[fam] = ops.get(fam, 0.0) + sec
    out = {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "device_ops": top(ops),
        "trace": trace,
        "idle_gaps": [],
    }
    if trace.anchor_s is not None and anchor_perf is not None:
        shift = trace.anchor_s - anchor_perf  # perf_counter -> trace clock
        gaps = idle_gaps(dev0, start_perf + shift, stop_perf + shift)
        spans = [(n, s + shift, e + shift, d) for n, s, e, d in host_spans]
        out["idle_gaps"] = top(attribute_gaps(gaps, spans))
        out["clock_shift_s"] = shift
    return out
