"""Serving benchmark: Poisson load over the continuous-batching engine
-> BENCH_SERVING.json.

Two rows, SAME request trace, same compiled programs, same paged pool:

- ``continuous`` — the real engine: requests join free decode lanes the
  step they arrive (serving/engine.py);
- ``static`` — the baseline everyone compares against: admission only
  into an EMPTY engine (``ServingEngine(static_batching=True)``), so a
  batch forms, runs until its LAST member finishes, and only then does
  the next batch start. The delta between the rows is therefore exactly
  what mid-flight join/leave buys — not a different model, sampler, or
  cache layout.

Load model: request arrivals are a seeded Poisson process (exponential
inter-arrival times at ``$DDL_SERVE_RATE`` req/s), prompt lengths and
``max_new_tokens`` drawn per-request from seeded ranges — the varied
completion lengths are what make static batching wait on stragglers.
The driver submits a request when the wall clock passes its arrival time
and otherwise steps the engine; TTFT clocks from SUBMISSION (arrival),
so queueing delay counts against both modes, as it does in production.

A third row, ``continuous``/``pallas``, replays the same trace with
``serving.attn_kernel='pallas'`` (ops/paged_attention.py — interpret
mode on CPU, so the row measures scheduling with the kernel code path
live, not kernel speed): same greedy trace, so its token stream must
match the reference row's exactly (pinned in the comparison block).

A fourth row, ``continuous``/``speculation=ngram:K``, replays the same
trace through the speculative draft-and-verify path
(serving.speculation) — the random-byte prompts are the ADVERSARIAL
workload for prompt-lookup drafting, so this row pins exact token
parity plus honest accept-rate reporting where drafting is hardest.
The ``speculation`` block then reruns speculative on/off on a
REPETITIVE-text trace (patterned prompts, long completions, saturating
arrival rate — the decode-bound regime speculation exists for) and pins
the headline: speculative decode tokens/s >= 1.25x the non-speculative
row there, token-for-token identical output on both workloads. ``decode_tokens_per_sec`` is decode-PHASE throughput
(generated tokens after the first, over the decode span histogram's
total wall time), so the ratio isolates what verify batching buys on
the hot loop from prefill/queueing effects.

The ``prefix_cache`` block is the shared-prefix KV reuse story
(serving.prefix_cache): a trace of M system prompts x N short suffixes
served cache-on and cache-off, plus the random-byte trace replayed
cache-on as the adversarial control. Pins: >= 2x prefill-token
reduction (prompt tokens / trie misses) and an improved p50 TTFT on
the shared trace, exact token parity on BOTH traces, an honestly ~0
hit rate on the control, and the widened compile pin
``len(prompt_buckets) + len(suffix_buckets) + 1`` with zero
steady-state recompiles.

The ``kv_hierarchy`` block is the memory-hierarchy story
(serving.spill_blocks): the shared-prefix workload widened to MORE
system prompts than the device pool can cache (the pool is rebuilt at
``_KV_DEVICE_BLOCKS`` via ``engine.constrain_pool`` after warmup), so
cache-off-duty prefixes are constantly evicted. Four rows on the SAME
trace and constrained pool: spill off (evicted prefixes go cold),
spill fp (evicted prefixes demote to host RAM and promote back on the
next warm admission), spill fp under a deliberately tiny host budget
(final evictions fire mid-trace), and spill int8 (the quantized codec).
Pins: spill-on recovers >= 2.0x the prefix hit tokens of spill-off,
exact token parity for the fp rows (the payload is bitwise) including
under final-eviction pressure, ``final_evictions > 0`` on the tight
row, an int8 promote logit probe inside the 5% tolerance, the int8
adversarial control (random-byte trace, constrained pool) reporting
``hit_rate == 0.0`` exactly, and the unchanged prefix compile pin with
zero steady-state recompiles on every row — promotes are eager
transfers, not programs.

The ``kv_quant`` block is the quantized device-pool story
(serving.kv_quant='int8'): the pool stores KV blocks as int8 with
per-(slot, head) f32 scales, so the SAME HBM budget mints ~3-4x the
blocks. Rows: the standard random-byte trace on an int8 pool (greedy
token parity vs the fp ``continuous`` row — quantized KV must not
change the tokens there), the kv-hierarchy shared-prefix trace on a
constrained int8 pool with and without the spill tier (the hierarchy
composes: int8 device blocks demote/promote bitwise through the fp
codec), and the random-byte trace through the int8+spill engine as the
adversarial control (``hit_rate == 0.0`` — no request's logits ride
reused quantized KV there). Pins: >= 2.0x budget-minted blocks vs the
fp pool (the capacity headline), token parity on the standard trace, a
measured cached-prefix logit-drift probe inside the 5% bar (suffix
prefill gathers the prefix from the quantized pool — the read path the
probe exercises is the Pallas/reference dequant), spill recovery >= 2x
on top of int8, and the unchanged compile pins with zero steady-state
recompiles (dequant is fused into the gather; no extra programs).

The ``router`` block is the scale-out story (serving/router.py): a
least-loaded + deadline-shedding ReplicaRouter over replicas in
``$DDL_SERVE_REPLICAS`` (default 1,2,4) replaying the trace at offered
loads of ``$DDL_SERVE_LOADS`` (default 1x/10x/100x) the base rate, every
request due ``$DDL_SERVE_SLO`` seconds after arrival. Replicas are
simulated as N PARALLEL CHIPS in virtual time (see ``_run_router`` — a
serial wall-clock driver is work-conserving on one host CPU and
mathematically cannot show scale-out), with each virtual step charged
the real measured host cost of that engine step. Pins: near-linear
fleet goodput scaling (4 replicas >= 3.0x one at 10x load), a non-zero
typed shed rate on the overloaded single replica at 100x, bounded p99
TTFT on every row that shed (admission control converts overload into
rejections, not latency), exact token parity of every served request
against a direct single-engine run, and the per-fleet AOT compile pin
``replicas * (buckets + 2)``.

Per row: requests/s and generated tokens/s over the makespan (first
arrival -> last completion), tokens/s/chip (this is a single-chip engine
— chips=1; the multi-chip story is data-parallel engine replicas, see
docs/SERVING.md), p50/p99 time-to-first-token, p50/p99 inter-token
latency, the per-PHASE host latency breakdown (p50/p99 of the engine's
schedule/prefill/decode telemetry spans — where a step's wall time goes,
which is what the max_prefills_per_step knob moves), block-pool
high-water mark, the decode executable's donated-leaf count from the
device registry (> 0 = the cache aliases input->output instead of
double-buffering the pool), and the compile counters proving steady
state ran from the AOT executable cache (zero recompiles).

CPU-sim caveat (same as every BENCH_* artifact here): absolute rates are
XLA:CPU numbers on a tiny model — meaningless as TPU predictions. The
CLAIM this artifact pins is relational and mechanism-level: continuous
beats static on throughput at equal-or-better p99 TTFT under the same
trace (tests/test_serving_bench.py re-asserts it on the committed file).

Usage: python tools/serve_bench.py   (writes BENCH_SERVING.json at the
repo root, or $DDL_SERVE_OUT; $DDL_SERVE_N requests, $DDL_SERVE_RATE
req/s, $DDL_SERVE_SEED trace seed, $DDL_SERVE_QUANT=int8 adds an int8
weight-quantized continuous row.)

``python tools/serve_bench.py --check`` re-validates an existing
artifact (the committed file or a fresh $DDL_SERVE_OUT) against the
pinned claim keys WITHOUT re-running the engines — the cheap CI gate
for artifact regeneration; exits non-zero listing every failed claim.
"""

from __future__ import annotations

import gc
import json
import math
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# CPU simulation: platform and device count are read when jax is imported,
# so they are set before the first `import jax`.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

_OUT = os.environ.get("DDL_SERVE_OUT", os.path.join(_REPO, "BENCH_SERVING.json"))
_N = int(os.environ.get("DDL_SERVE_N", "48"))
# 75 req/s base: ~0.4x the single engine's measured CPU-sim capacity
# (~185 req/s saturated, speculation on), so the router sweep's 10x
# multiplier offers ~4x what ONE replica can serve — the regime where a
# 4-replica fleet shows near-linear scaling. At a lower base, 10x sits
# below fleet capacity and the sweep measures the arrival window, not
# scale-out.
_RATE = float(os.environ.get("DDL_SERVE_RATE", "75"))
_SEED = int(os.environ.get("DDL_SERVE_SEED", "0"))
_QUANT_ROW = os.environ.get("DDL_SERVE_QUANT", "") == "int8"

# The serving workload: gpt2 tiny, byte vocab — the engine's mechanics
# (paging, bucketing, admission) are model-size-independent, and a tiny
# model keeps the full Poisson run inside the slow-test budget.
_MODEL_KW = dict(size="tiny", vocab_size=256, max_len=160)
_SERVING_KW = dict(
    slots=4, block_size=16, hbm_budget_mb=8, max_seq_len=96,
    prompt_buckets=(16, 32),
)
_PROMPT_LEN = (4, 31)      # inclusive range, spans both buckets
_MAX_NEW = (8, 33)         # varied completions: static waits on stragglers
# Speculation: drafts per lane per verify step (serving.speculation).
_SPEC_K = 4
# The repetitive-text workload (the speculation block): prompts are a
# short byte pattern tiled to length, completions run long, and arrivals
# come at a SATURATING rate — the regime prompt-lookup drafting exists
# for (copied spans, loops, boilerplate, decode-bound load). The rate
# matters for the headline's honesty in the other direction: at trickle
# load every lane runs alone and the decode-phase column mostly measures
# per-call dispatch overhead, which understates what verify batching
# buys precisely when there is nothing to batch.
_REP_PATTERN = (3, 5)      # pattern period range (tokens)
_REP_PROMPT_LEN = (8, 16)  # fits the first bucket
_REP_MAX_NEW = (48, 77)    # long completions, still inside max_seq_len
_REP_RATE = _RATE * 3.0    # keeps all slots occupied (decode-bound)
# The shared-prefix workload (the prefix_cache block): M system prompts
# of _PX_PREFIX_LEN tokens, each followed by short per-request suffixes —
# the agent/chat shape the prefix trie exists for. Served twice, cache on
# and cache off, under the same trace; the headline is the prefill-token
# reduction (total prompt tokens / tokens actually prefilled) plus an
# improved p50 TTFT, at exact token parity. The ADVERSARIAL control
# replays the random-byte trace through the cache-on engine: every
# prompt is unique, so the honest hit rate there is ~0 and the artifact
# shows the cache reporting a miss-only workload truthfully.
_PX_SERVING_KW = dict(
    slots=4, block_size=16, hbm_budget_mb=8, max_seq_len=96,
    prompt_buckets=(16, 32, 64), prefix_cache=True, suffix_buckets=(8,),
)
_PX_SERVING_OFF = {k: v for k, v in _PX_SERVING_KW.items()
                   if k not in ("prefix_cache", "suffix_buckets")}
_PX_PREFIXES = 4           # distinct system prompts in the trace
_PX_PREFIX_LEN = 32        # whole blocks (2 x block_size) -> cacheable
_PX_SUFFIX_LEN = (2, 9)    # per-request tail, fits the 8-wide suffix bucket
# The KV-hierarchy workload (the kv_hierarchy block): the shared-prefix
# shape with MORE prefixes than the constrained device pool can hold.
# 8 prefixes x 2 blocks = 16 blocks of prefix KV against a pool
# constrained to _KV_DEVICE_BLOCKS (23 usable; 4 lanes x 5 blocks of
# active demand leaves single-digit cache headroom), so the off-duty
# prefixes are always under eviction pressure. The default spill budget
# ($DDL_SERVE_SPILL_BLOCKS) holds the full prefix working set; the
# tight row's budget holds two prefixes, forcing final evictions.
_KV_PREFIXES = 8
_KV_DEVICE_BLOCKS = 24
_SPILL_BLOCKS = int(os.environ.get("DDL_SERVE_SPILL_BLOCKS", "24"))
_KV_TIGHT_BLOCKS = 4
_KV_INT8_TOL = 0.05        # int8 promote logit-drift bar (relative)
# The kv trace needs enough revisits per prefix for spill->promote round
# trips to dominate; floor the trace length at 2 visits per prefix so a
# shrunken smoke _N still exercises the hierarchy end to end.
_KV_N = int(os.environ.get(
    "DDL_SERVE_KV_N", str(max(_N, 2 * _KV_PREFIXES))
))
# The router scale-out sweep (serving/router.py): offered-load
# multipliers x replica counts, every request carrying an SLO deadline
# of arrival + _SLO_S. All three knobs shrink for CI smoke runs.
_REPLICAS = tuple(
    int(x) for x in os.environ.get("DDL_SERVE_REPLICAS", "1,2,4").split(",")
)
_LOADS = tuple(
    float(x) for x in os.environ.get("DDL_SERVE_LOADS", "1,10,100").split(",")
)
_SLO_S = float(os.environ.get("DDL_SERVE_SLO", "0.25"))
# The router sweep replays a LONGER trace (4x the wall rows' _N): the
# goodput denominator is the virtual makespan, and with a short trace
# the last wave's drain time dominates the arrival window, flooring
# every fleet's makespan at the same per-request latency — scale-out
# only becomes measurable when the window amortizes the tail.
_ROUTER_N = int(os.environ.get("DDL_SERVE_ROUTER_N", str(4 * _N)))
# The socket-fleet block (serving/worker.py + SocketReplica): REAL child
# processes behind real sockets, measured on the WALL CLOCK. The CPU sim
# runs on a single host core, where N CPU-bound processes just
# time-share — so each worker sleeps $DDL_SERVE_DWELL seconds after
# every engine step, the sim's stand-in for device program latency (a
# real TPU step is device-bound while the host waits). That makes the
# workload latency-bound, and the wall-clock scale-out the block pins is
# genuine cross-process overlap of those dwells, not an assumed speedup.
# The artifact records the timebase and dwell next to every row.
_FLEET_SIZES = tuple(
    int(x) for x in os.environ.get("DDL_SERVE_FLEET", "1,2,4").split(",")
    if x.strip()
)  # DDL_SERVE_FLEET="" skips the fleet block (the tier-1 smoke leg:
#    the transport itself is pinned by tests/test_serving_worker.py)
_FLEET_N = int(os.environ.get("DDL_SERVE_FLEET_N", "48"))
_FLEET_DWELL = float(os.environ.get("DDL_SERVE_DWELL", "0.05"))
# Saturating Poisson load: arrivals an order of magnitude faster than
# one dwell-bound worker can serve, so queues never empty mid-run and
# tokens/s measures service capacity, not the arrival window.
_FLEET_RATE = float(os.environ.get("DDL_SERVE_FLEET_RATE", "400"))
_FLEET_SLO = float(os.environ.get("DDL_SERVE_FLEET_SLO", "0.5"))
_FLEET_SERVING_KW = dict(
    slots=4, block_size=16, hbm_budget_mb=8, max_seq_len=96,
    prompt_buckets=(16, 32), heartbeat_interval_s=0.05,
    heartbeat_timeout_s=30.0,
)
# The disaggregation block (serving.role + paged KV-block handoff): the
# long-prompt burst workload where unified serving is structurally worst
# — every admission runs a long prefill INSIDE the shared step loop, so
# active decode lanes stall a full prompt's prefill between two of their
# own tokens. The A/B is two same-size socket fleets over the SAME trace
# and oracle: N unified workers vs 1 prefill + (N-1) decode workers with
# KV shipped block-wise over the wire. The headline is decode-phase
# inter-token latency (gaps BETWEEN generated tokens, TTFT excluded):
# decode-role workers never run a long prefill, so their lanes tick at
# the decode cadence. Timebase: wall clock + the per-step dwell of the
# fleet block, PLUS a per-prefilled-token dwell on every worker of both
# fleets (real prefill time grows with uncached prompt length while a
# decode step is ~flat; without this the tiny CPU model's prefill is
# nearly free and NO serving architecture could show a prefill-
# interference delta). DDL_SERVE_DISAGG="" skips the block.
_DISAGG_ON = bool(os.environ.get("DDL_SERVE_DISAGG", "1").strip())
_DISAGG_WORKERS = int(os.environ.get("DDL_SERVE_DISAGG_WORKERS", "4"))
_DISAGG_N = int(os.environ.get("DDL_SERVE_DISAGG_N", "24"))
# Burst arrivals: the whole trace lands in well under the time one
# prefill-dwell-bound worker needs to chew through it, so admissions
# keep interleaving with live decode lanes for the entire run.
_DISAGG_RATE = float(os.environ.get("DDL_SERVE_DISAGG_RATE", "40"))
_DISAGG_PROMPT_LEN = (48, 89)   # long, unique prompts (prefill-heavy)
_DISAGG_MAX_NEW = (16, 25)
# Seconds per prefilled token: at 0.01 a 64-token prompt costs ~13
# decode steps, which puts the unified fleet's admission stalls well
# above the single-core harness's scheduling-noise tail (~0.5s spikes
# hit BOTH fleets; at 0.002 the real interference signal drowned in it).
_DISAGG_PREFILL_DWELL = float(
    os.environ.get("DDL_SERVE_PREFILL_DWELL", "0.01")
)
_DISAGG_SERVING_KW = dict(
    slots=4, block_size=16, hbm_budget_mb=8, max_seq_len=128,
    prompt_buckets=(64, 96), prefix_cache=True, suffix_buckets=(8,),
    heartbeat_interval_s=0.05, heartbeat_timeout_s=30.0,
)


def _make_trace(seed: int, rate: float, n: int = _N):
    """The request trace rows replay: (arrival_s, prompt, max_new).

    Seeded PER RUN (the seed is recorded next to every row/block that
    consumed it, so any artifact number can be regenerated bit-exactly).
    ``rate`` only scales the exponential inter-arrival gaps — the rng
    stream is consumed identically at every rate, so the SAME seed at
    10x/100x load yields the SAME prompts and completion lengths with
    arrivals compressed: the router scale-out rows are a pure A/B on
    offered load."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n):
        plen = int(rng.integers(*_PROMPT_LEN))
        prompt = [int(t) for t in rng.integers(1, 256, plen)]
        max_new = int(rng.integers(*_MAX_NEW))
        trace.append((float(arrivals[i]), prompt, max_new))
    return trace


def _make_repetitive_trace(seed: int):
    """Same Poisson arrivals, REPETITIVE prompts: a random pattern of a
    few bytes tiled to prompt length, so the trailing n-gram always
    recurs and the draft source has something real to copy."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / _REP_RATE, _N)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(_N):
        period = int(rng.integers(*_REP_PATTERN))
        pattern = [int(t) for t in rng.integers(1, 256, period)]
        plen = int(rng.integers(*_REP_PROMPT_LEN))
        prompt = (pattern * (plen // period + 1))[:plen]
        max_new = int(rng.integers(*_REP_MAX_NEW))
        trace.append((float(arrivals[i]), prompt, max_new))
    return trace


def _make_disagg_trace(seed: int):
    """The long-prompt burst (the disagg block): unique random prompts
    of _DISAGG_PROMPT_LEN tokens at _DISAGG_RATE Poisson arrivals —
    prefill-heavy, nothing shared, so the unified fleet's prefix cache
    absorbs none of it and every admission is a full-length prefill."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / _DISAGG_RATE, _DISAGG_N)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(_DISAGG_N):
        plen = int(rng.integers(*_DISAGG_PROMPT_LEN))
        prompt = [int(t) for t in rng.integers(1, 256, plen)]
        max_new = int(rng.integers(*_DISAGG_MAX_NEW))
        trace.append((float(arrivals[i]), prompt, max_new))
    return trace


def _make_shared_prefix_trace(seed: int):
    """Poisson arrivals over M shared system prompts: request i carries
    prefix ``i % M`` plus a short random suffix, so every prefix's first
    arrival runs cold and later arrivals share its first two blocks.
    Round-robin prefix order spreads the cold misses across the head of
    the trace instead of front-loading them on one prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / _RATE, _N)
    arrivals = np.cumsum(gaps)
    prefixes = [
        [int(t) for t in rng.integers(1, 256, _PX_PREFIX_LEN)]
        for _ in range(_PX_PREFIXES)
    ]
    trace = []
    for i in range(_N):
        slen = int(rng.integers(*_PX_SUFFIX_LEN))
        suffix = [int(t) for t in rng.integers(1, 256, slen)]
        max_new = int(rng.integers(*_MAX_NEW))
        trace.append((
            float(arrivals[i]), prefixes[i % _PX_PREFIXES] + suffix,
            max_new,
        ))
    return trace


def _make_kv_trace(seed: int):
    """The shared-prefix trace at _KV_PREFIXES system prompts: request i
    carries prefix ``i % _KV_PREFIXES``, so by the time a prefix recurs
    the constrained device pool has evicted it — every warm admission is
    a spill-tier round trip when the hierarchy is on, and a cold refill
    when it is off."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / _RATE, _KV_N)
    arrivals = np.cumsum(gaps)
    prefixes = [
        [int(t) for t in rng.integers(1, 256, _PX_PREFIX_LEN)]
        for _ in range(_KV_PREFIXES)
    ]
    trace = []
    for i in range(_KV_N):
        slen = int(rng.integers(*_PX_SUFFIX_LEN))
        suffix = [int(t) for t in rng.integers(1, 256, slen)]
        max_new = int(rng.integers(*_MAX_NEW))
        trace.append((
            float(arrivals[i]), prefixes[i % _KV_PREFIXES] + suffix,
            max_new,
        ))
    return trace


def _percentiles(xs):
    import numpy as np

    if not xs:
        return {"p50": None, "p99": None}
    return {
        "p50": round(float(np.percentile(xs, 50)), 6),
        "p99": round(float(np.percentile(xs, 99)), 6),
    }


def _exact_pcts(xs):
    """Ceil-rank order statistics (rank ``ceil(q/100 * n)``, 1-based) —
    the EXACT counterpart of ``LatencyHistogram.percentile``'s
    definition, so the hist-vs-exact pin below is a clean
    one-bucket-relative-error bound. Not ``np.percentile``: every numpy
    method interpolates positions over ``n - 1`` gaps, a different
    statistic whose gap vs ceil-rank is unbounded at small n."""
    if not xs:
        return {"p50": None, "p99": None}
    s = sorted(float(x) for x in xs)
    def pick(q):
        rank = max(1, math.ceil(q / 100.0 * len(s)))
        return round(s[rank - 1], 6)
    return {"p50": pick(50), "p99": pick(99)}


def _hist_pcts(h):
    """p50/p99 from a ``telemetry.LatencyHistogram`` (the SLO-grade
    streaming sketch — O(buckets) memory, mergeable across processes;
    replaces the store-every-sample math for the latency columns)."""
    if h is None or not h.count:
        return {"p50": None, "p99": None}
    return {
        "p50": round(h.percentile(50), 6),
        "p99": round(h.percentile(99), 6),
    }


def _hist_vs_exact(h, xs):
    """The satellite pin: every histogram percentile within one bucket's
    relative width of the exact ceil-rank order statistic."""
    if h is None or not h.count or not xs:
        return {"max_rel_dev": None, "bound": None, "ok": None}
    hist, exact = _hist_pcts(h), _exact_pcts(xs)
    devs = [
        abs(hist[k] / exact[k] - 1.0)
        for k in ("p50", "p99") if exact[k]
    ]
    bound = h.rel_error
    return {
        "max_rel_dev": round(max(devs), 6) if devs else 0.0,
        "bound": round(bound, 6),
        "ok": bool(devs and max(devs) <= bound + 1e-9 or not devs),
    }


def _token_checksum(finished):
    """CRC of every request's token stream, in request-id order — equal
    checksums mean token-for-token identical output."""
    import zlib

    import numpy as np

    toks = [t for s in finished for t in [-1] + s.generated]  # -1 delimits
    return int(zlib.crc32(np.asarray(toks, np.int64).tobytes()))


def _phase_latency_ms(tel):
    """p50/p99 of each engine phase's host wall time, from the per-phase
    latency HISTOGRAMS the telemetry bundle feeds at every span close
    (schedule / prefill / decode) — no span ring walk, no stored samples,
    and the same numbers a fleet merge of N engines would report."""
    out = {}
    for phase in ("schedule", "prefill", "decode"):
        h = tel.hists.get(phase)
        if h is None or not h.count:
            continue
        p = _hist_pcts(h)
        out[phase] = {k: (None if v is None else round(v * 1e3, 4))
                      for k, v in p.items()}
    return out


def _run_mode(model, params, trace, *, static: bool, quant: str = "none",
              kernel: str = "reference", speculation: str = "off",
              serving_kw: dict | None = None,
              constrain_blocks: int | None = None,
              promote_async: bool | None = None):
    import tempfile

    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import Request, ServingEngine
    from distributeddeeplearning_tpu.telemetry import Telemetry

    cfg = ServingConfig(**(serving_kw or _SERVING_KW), quant=quant,
                        attn_kernel=kernel, speculation=speculation)
    # Enabled telemetry per row: the span ring is the source of the
    # per-phase latency columns (sized for the whole run, not just the
    # flight-recorder tail), and the registry carries the decode
    # executable's donation counter.
    tel = Telemetry(
        enabled=True, out_dir=tempfile.mkdtemp(prefix="serve_bench_tel_"),
        ring_size=1 << 17,
    )
    engine = ServingEngine(
        model, params, cfg, seed=_SEED, static_batching=static,
        telemetry=tel,
    )
    if promote_async is not None:
        # The async-promote A/B (ROADMAP 2b): False restores the
        # upload-at-prefill-dispatch baseline, so promote_wait measures
        # the host stall async staging removes from the dispatch path.
        engine.promote_async = promote_async
    engine.warmup()  # compiles happen HERE, outside the timed window
    if constrain_blocks is not None:
        # The kv_hierarchy rows shrink the device pool AFTER warmup (the
        # compiled programs are pool-size-agnostic — the pool is data),
        # so eviction pressure is a workload knob, not an hbm budget.
        engine.constrain_pool(constrain_blocks)
    compiles_before = engine.num_compiles
    # Collect BEFORE the timed loop: the previous rows' dead engines and
    # caches otherwise surface as collector pauses inside THIS row's
    # spans, and not uniformly — spans that allocate on the host (the
    # speculative verify path's acceptance loop) absorb more of them
    # than spans that don't. That is benchmark-process hygiene, not an
    # engine cost, so it must not land in the latency columns.
    gc.collect()

    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    engine.clock = clock
    i = 0
    while i < len(trace) or not engine.scheduler.idle:
        now = clock()
        while i < len(trace) and trace[i][0] <= now:
            _, prompt, max_new = trace[i]
            engine.submit(Request(prompt=prompt, max_new_tokens=max_new))
            i += 1
        if not engine.step() and i < len(trace):
            # Idle before the next arrival: sleep up to it (don't busy-spin
            # the clock — idle gaps belong to the load, not the engine).
            time.sleep(max(0.0, min(trace[i][0] - clock(), 0.01)))
    makespan = clock() - trace[0][0]

    finished = sorted(
        engine.scheduler.finished, key=lambda s: s.request.request_id
    )
    assert len(finished) == len(trace), engine.stats()
    per_req = [s.metrics() for s in finished]
    gen_tokens = sum(m["new_tokens"] for m in per_req)
    ttfts = [m["ttft_s"] for m in per_req]
    itls = [x for m in per_req for x in m["inter_token_s"]]
    stats = engine.stats()
    decode_reg = tel.registry.get("serving_decode") or {}
    ttft_hist = tel.hists.get("ttft")
    # Decode-PHASE throughput: tokens produced by decode/verify calls
    # (everything after each request's prefill-sampled first token) over
    # the decode span histogram's total wall time. This is the column
    # speculation moves — makespan throughput also carries prefill and
    # queueing, which drafting cannot touch.
    decode_hist = tel.hists.get("decode")
    decode_wall = float(decode_hist.sum) if decode_hist else 0.0
    decode_tokens = gen_tokens - len(per_req)
    spec = stats["speculation"]
    return {
        "mode": "static" if static else "continuous",
        "kernel": kernel,
        "quant": quant,
        "speculation": speculation,
        "prefix_cache": bool(cfg.prefix_cache),
        # Trie counters (None with the cache off): hit/miss prompt
        # tokens, hit rate, decode-route admissions, eviction totals.
        "prefix": stats.get("prefix_cache"),
        "prompt_tokens": sum(len(p) for _, p, _ in trace),
        # Deterministic greedy trace: the pallas row must reproduce the
        # reference row's tokens exactly — compared as a checksum so the
        # artifact pins the claim without embedding ~1k tokens.
        "token_checksum": _token_checksum(finished),
        "requests": len(per_req),
        "generated_tokens": gen_tokens,
        "makespan_s": round(makespan, 4),
        "requests_per_sec": round(len(per_req) / makespan, 3),
        "tokens_per_sec": round(gen_tokens / makespan, 2),
        # Single-chip engine: per-chip == total (multi-chip = replicas).
        "chips": 1,
        "tokens_per_sec_per_chip": round(gen_tokens / makespan, 2),
        # The SLO columns are histogram-derived (telemetry.LatencyHistogram
        # — the engine records TTFT at first token); the exact ceil-rank
        # order statistics ride along so the one-bucket-relative-error
        # agreement is pinned IN the artifact, not just in tests.
        "ttft_s": _hist_pcts(ttft_hist),
        "ttft_exact_s": _exact_pcts(ttfts),
        "ttft_hist_vs_exact": _hist_vs_exact(ttft_hist, ttfts),
        "inter_token_s": _percentiles(itls),
        "queue_s": _hist_pcts(tel.hists.get("queue_wait")),
        "block_high_water": stats["block_high_water"],
        "num_blocks": stats["num_blocks"],
        "constrained_blocks": constrain_blocks,
        # Pool layout columns: budget-minted block count above is the
        # capacity headline's numerator/denominator (constrain_pool only
        # swaps the scheduler's pool — stats reports the minted count).
        "kv_quant": stats["kv_quant"],
        "kv_bytes_per_token": stats["kv_bytes_per_token"],
        "phase_latency_ms": _phase_latency_ms(tel),
        # Host stall per promoted admission at prefill dispatch (None
        # when nothing promoted): with promote_async the upload was
        # staged at admission and only the scatter remains here.
        "promote_async": bool(engine.promote_async),
        "promote_wait_ms": (
            {k: (None if v is None else round(v * 1e3, 4))
             for k, v in _hist_pcts(tel.hists["promote_wait"]).items()}
            if tel.hists.get("promote_wait")
            and tel.hists["promote_wait"].count else None
        ),
        # Admission-time staging cost (async rows only: the upload
        # dispatch moved OFF the prefill-dispatch path and is recorded
        # here instead).
        "promote_stage_ms": (
            {k: (None if v is None else round(v * 1e3, 4))
             for k, v in _hist_pcts(tel.hists["promote_stage"]).items()}
            if tel.hists.get("promote_stage")
            and tel.hists["promote_stage"].count else None
        ),
        "decode_donated_args": int(decode_reg.get("donated_args", 0)),
        "compiles_warmup": compiles_before,
        "compiles_after_run": stats["num_compiles"],  # must equal warmup
        "decode_calls": stats["calls"]["decode"],
        "verify_calls": stats["calls"]["verify"],
        "prefill_calls": stats["calls"]["prefill"],
        "decode_tokens_per_sec": (
            round(decode_tokens / decode_wall, 2) if decode_wall else None
        ),
        # Speculation columns (None on non-speculative rows): fraction of
        # drafted tokens accepted, and mean tokens emitted per lane per
        # verify step (1 = drafting bought nothing, K+1 = full window).
        "accept_rate": None if spec is None else spec["accept_rate"],
        "mean_accepted_per_step": (
            None if spec is None else spec["mean_accepted_per_step"]
        ),
        "quant_report": stats["quant"],
    }


def _int8_promote_probe(model, params):
    """The int8 codec bar, measured: seed a prefix, force it to spill,
    re-admit warm (promote through the codec), and compare the suffix
    prefill's last-position logits against the fp codec's (fp payloads
    are bitwise, so the fp run IS the unquantized reference). Mirrors
    tests/test_serving_spill.py::test_int8_promote_within_logit_tolerance
    so the committed artifact carries the number the test pins."""
    import numpy as np

    import jax.numpy as jnp
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.generate import logits_at, prefill
    from distributeddeeplearning_tpu.serving import Request, ServingEngine

    def logits(codec):
        cfg = ServingConfig(**_PX_SERVING_KW, spill_blocks=_SPILL_BLOCKS,
                            spill_codec=codec)
        eng = ServingEngine(model, params, cfg, seed=_SEED)
        eng.warmup()
        eng.constrain_pool(_KV_DEVICE_BLOCKS)
        rng = np.random.default_rng(_SEED + 4)
        prefix = [int(t) for t in rng.integers(1, 256, _PX_PREFIX_LEN)]
        eng.submit(Request(prompt=prefix + [50, 51], max_new_tokens=2))
        eng.run()
        pool = eng.scheduler.pool
        got = pool.alloc(pool.free_blocks + pool.evictable_blocks)
        pool.free(got)
        assert pool.spilled_blocks >= 2, "prefix never spilled"
        eng.submit(Request(prompt=prefix + [60, 61], max_new_tokens=2))
        (st,) = eng.scheduler.admit(
            0.0, eng.bucket_of, suffix_bucket_of=eng.suffix_bucket_of,
            cover_tokens=eng.pages * eng.block_size,
        )
        assert st.promoted, "warm admission did not cross the host tier"
        eng._apply_promotions(st)
        row = np.zeros((eng.pages,), np.int32)
        chain = st.cached_blocks + st.blocks
        row[:len(chain)] = chain
        suffix = st.request.prompt[st.cached_len:]
        tokens = np.zeros((1, st.bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        cache1 = eng._inject(eng._cache, row[None],
                             np.int32([st.cached_len]))
        out, _ = prefill(eng.model, eng._dequant(eng._params), cache1,
                         jnp.asarray(tokens))
        return np.asarray(
            logits_at(out, jnp.asarray(np.int32([len(suffix) - 1]))),
            np.float32,
        )

    ref, quant = logits("fp"), logits("int8")
    scale = float(np.abs(ref).max())
    drift = float(np.abs(ref - quant).max())
    rel = drift / scale if scale else 0.0
    return {
        "max_abs_logit_drift": round(drift, 6),
        "fp_logit_scale": round(scale, 6),
        "max_rel_drift": round(rel, 6),
        "tolerance": _KV_INT8_TOL,
        "ok": bool(rel <= _KV_INT8_TOL),
    }


def _kv_quant_drift_probe(model, params):
    """The int8 POOL bar, measured: seed a shared prefix so its KV lives
    in the device pool (quantized at scatter when kv_quant='int8'), then
    admit a second request on the same prefix and compare the suffix
    prefill's last-position logits against the fp pool's. The suffix
    prefill GATHERS the cached prefix from the pool, so this is the
    dequant read path (ops/paged_attention.py) under real engine state —
    the number tests/test_serving.py pins, carried in the artifact."""
    import numpy as np

    import jax.numpy as jnp
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.generate import logits_at, prefill
    from distributeddeeplearning_tpu.serving import Request, ServingEngine

    def logits(kv_quant):
        cfg = ServingConfig(**_PX_SERVING_KW, kv_quant=kv_quant)
        eng = ServingEngine(model, params, cfg, seed=_SEED)
        eng.warmup()
        rng = np.random.default_rng(_SEED + 5)
        prefix = [int(t) for t in rng.integers(1, 256, _PX_PREFIX_LEN)]
        eng.submit(Request(prompt=prefix + [50, 51], max_new_tokens=2))
        eng.run()
        eng.submit(Request(prompt=prefix + [60, 61], max_new_tokens=2))
        (st,) = eng.scheduler.admit(
            0.0, eng.bucket_of, suffix_bucket_of=eng.suffix_bucket_of,
            cover_tokens=eng.pages * eng.block_size,
        )
        assert st.cached_len >= 2 * eng.block_size, "prefix not cached"
        row = np.zeros((eng.pages,), np.int32)
        chain = st.cached_blocks + st.blocks
        row[:len(chain)] = chain
        suffix = st.request.prompt[st.cached_len:]
        tokens = np.zeros((1, st.bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        cache1 = eng._inject(eng._cache, row[None],
                             np.int32([st.cached_len]))
        out, _ = prefill(eng.model, eng._dequant(eng._params), cache1,
                         jnp.asarray(tokens))
        return np.asarray(
            logits_at(out, jnp.asarray(np.int32([len(suffix) - 1]))),
            np.float32,
        )

    ref, quant = logits("off"), logits("int8")
    scale = float(np.abs(ref).max())
    drift = float(np.abs(ref - quant).max())
    rel = drift / scale if scale else 0.0
    return {
        "max_abs_logit_drift": round(drift, 6),
        "fp_logit_scale": round(scale, 6),
        "max_rel_drift": round(rel, 6),
        "tolerance": _KV_INT8_TOL,
        "ok": bool(rel <= _KV_INT8_TOL),
    }


def _reference_tokens(model, params, trace):
    """The parity oracle: the SAME prompts run to completion on ONE
    engine directly — no router, no deadlines, no speculation. Because
    sampling is keyed per request id (rng = fold_in(seed, request_id)),
    every router row's greedy tokens must match these token-for-token
    regardless of which replica served them or who their batchmates
    were."""
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import Request, ServingEngine

    cfg = ServingConfig(**_SERVING_KW)
    engine = ServingEngine(model, params, cfg, seed=_SEED)
    for j, (_, prompt, max_new) in enumerate(trace):
        engine.submit(
            Request(prompt=list(prompt), max_new_tokens=max_new,
                    request_id=j)
        )
    finished = engine.run()
    assert len(finished) == len(trace), engine.stats()
    return {s.request.request_id: list(s.generated) for s in finished}


def _run_router(model, params, trace, *, replicas: int, load_x: float,
                trace_seed: int, ref_tokens: dict):
    """One router scale-out row: ``replicas`` engines behind a
    least-loaded + deadline-shedding ReplicaRouter, replaying ``trace``
    with every request due at ``arrival + _SLO_S``.

    Timebase: a VIRTUAL-TIME discrete-event simulation of N parallel
    chips. N in-process replicas stepped serially on one host CPU are
    work-conserving — aggregate wall-clock throughput is flat in N, so a
    wall-clock driver can never show scale-out. Instead each replica
    carries its own virtual clock ``v[i]``; the event loop always
    advances the LEAST-advanced busy replica, measuring the real host
    wall time of that one ``step_replica`` call and charging it to
    ``v[i]`` alone (the step really would run concurrently on chip i);
    arrivals fire when their timestamp passes the busy-clock frontier,
    and an idle replica's clock jumps forward to the arrival it gets.
    Goodput = served tokens / virtual makespan, so scaling comes from
    real measured per-chip step costs, not an assumed speedup."""
    import tempfile

    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import (
        Request, ReplicaRouter, RequestShed,
    )
    from distributeddeeplearning_tpu.telemetry import LatencyHistogram

    cfg = ServingConfig(
        **_SERVING_KW, speculation=f"ngram:{_SPEC_K}", replicas=replicas,
        router_policy="least_loaded", shed_policy="deadline",
        shed_percentile=50.0,
    )
    tdir = tempfile.mkdtemp(prefix="serve_bench_router_")
    router = ReplicaRouter(model, params, cfg, seed=_SEED,
                           telemetry_dir=tdir)
    router.warmup()  # compiles happen HERE, outside the virtual clocks
    compiles_warmup = router.num_compiles
    # Prime the runtime: warmup AOT-compiles but never EXECUTES, and the
    # first execution of each program pays one-time backend/allocation
    # cost (~10x a steady step on CPU) — which would land in the latency
    # histograms exactly when the burst arrives and poison the shed
    # estimator's prefill percentile. One throwaway request per bucket
    # per replica, run to completion directly on each engine, then the
    # histograms and finished lists are wiped so the measured run starts
    # from a warm runtime and clean telemetry.
    for rep in router.replicas:
        for b_i, bucket in enumerate(_SERVING_KW["prompt_buckets"]):
            rep.engine.submit(Request(
                prompt=[1] * (bucket - 2), max_new_tokens=6,
                request_id=10**9 + rep.index * 10 + b_i,
            ))
        while rep.engine.step():
            pass
        rep.engine.scheduler.finished.clear()
        rep.telemetry.hists.clear()
    gc.collect()

    v = [0.0] * replicas   # per-replica virtual clocks (N chips)
    now = [0.0]            # the arrival frontier (last event dispatched)
    # Replica i's engine reads max(v[i], now): during ITS step now == v[i]
    # (span timestamps advance with the chip), and at submit time
    # now == the arrival — an idle chip's admission timestamps the
    # arrival, not its stale last-busy instant.
    router.set_clock(
        lambda: now[0],
        per_replica=lambda i: (lambda: max(v[i], now[0])),
    )
    shed = 0
    i = 0
    inf = float("inf")
    while True:
        busy = [
            k for k in range(replicas)
            if not router.replicas[k].quarantined
            and not router.replicas[k].engine.scheduler.idle
        ]
        t_arr = trace[i][0] if i < len(trace) else inf
        v_min = min((v[k] for k in busy), default=inf)
        if t_arr == inf and not busy:
            break
        if t_arr <= v_min:
            arr, prompt, max_new = trace[i]
            now[0] = arr
            try:
                router.submit(Request(
                    prompt=list(prompt), max_new_tokens=max_new,
                    request_id=i, deadline_s=arr + _SLO_S,
                ))
                # The chip that took it cannot have started before the
                # arrival existed: an idle clock jumps forward to it.
                tgt = router.routes[i]
                v[tgt] = max(v[tgt], arr)
            except RequestShed:
                shed += 1
            i += 1
        else:
            k = min(busy, key=lambda j: v[j])
            now[0] = v[k]
            t0 = time.perf_counter()
            router.step_replica(k)
            v[k] += time.perf_counter() - t0

    finished = router.finished()
    served_tokens = sum(len(s.generated) for s in finished)
    last_finish = max((s.finish_s for s in finished), default=trace[0][0])
    makespan = max(last_finish - trace[0][0], 1e-9)
    dropped = sum(
        len(r.engine.scheduler.dropped) for r in router.replicas
    )
    # Fleet p99 TTFT: the per-replica histograms MERGED (the same union
    # telemetry_aggregate.build_fleet performs on the stamped artifacts).
    merged = LatencyHistogram()
    for r in router.replicas:
        h = r.telemetry.hists.get("ttft")
        if h is not None and h.count:
            merged.merge(h)
    ttft_exact = [
        s.first_token_s - s.arrival_s
        for s in finished if s.first_token_s is not None
    ]
    stats = router.stats()
    router.write_trace()
    return {
        "replicas": replicas,
        "load_x": load_x,
        "rate_req_per_s": _RATE * load_x,
        "trace_seed": trace_seed,
        "slo_s": _SLO_S,
        "router_policy": "least_loaded",
        "shed_policy": "deadline",
        "speculation": f"ngram:{_SPEC_K}",
        "requests": len(trace),
        "served": len(finished),
        "shed": shed,
        "shed_rate": round(shed / len(trace), 4),
        "dropped_in_queue": dropped,
        "served_tokens": served_tokens,
        "virtual_makespan_s": round(makespan, 4),
        "goodput_tokens_per_sec": round(served_tokens / makespan, 2),
        "ttft_s": _hist_pcts(merged),
        "ttft_exact_s": _exact_pcts(ttft_exact),
        "tokens_match_reference": all(
            list(s.generated) == ref_tokens[s.request.request_id]
            for s in finished
        ),
        "compiles_warmup": compiles_warmup,
        "compiles_after_run": router.num_compiles,
        # Per-fleet AOT pin: each replica compiles its prefill-per-bucket
        # programs + decode + verify (speculation on), nothing after.
        "compile_pin": replicas * (len(_SERVING_KW["prompt_buckets"]) + 2),
        "rerouted": stats["rerouted"],
        "failed": stats["failed"],
    }


def _fleet_spec(extra_serving=None, base=None):
    """The --spec-json payload every fleet worker AND the parity oracle
    boot from: same model kwargs, same serving kwargs, same seed-init
    params — numerics cannot diverge between a worker and the oracle."""
    serving = {k: list(v) if isinstance(v, tuple) else v
               for k, v in (base or _FLEET_SERVING_KW).items()}
    if extra_serving:
        serving.update(extra_serving)
    return {
        "model": {"name": "gpt2", "kwargs": dict(_MODEL_KW)},
        "serving": serving,
    }


def _fleet_oracle_tokens(trace, base=None):
    """The fleet parity reference: a direct single-engine run of the
    SAME request list in a SUBPROCESS via ``serving.worker --oracle`` —
    the same pinned process environment the workers get, so the oracle
    measures the transport, not build-path drift."""
    import subprocess

    payload = json.dumps({"requests": [
        {"prompt": prompt, "max_new_tokens": max_new, "request_id": i}
        for i, (_, prompt, max_new) in enumerate(trace)
    ]})
    out = subprocess.run(
        [sys.executable, "-m",
         "distributeddeeplearning_tpu.serving.worker",
         "--oracle", "--spec-json", json.dumps(_fleet_spec(base=base)),
         "--seed", str(_SEED)],
        input=payload, capture_output=True, text=True, check=True,
    )
    for line in out.stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("event") == "oracle_result":
            return {int(k): v for k, v in rec["results"].items()}
    raise RuntimeError("oracle subprocess printed no oracle_result")


def _run_fleet(n_workers: int, trace, ref_tokens, *,
               telemetry_dir=None, shed: bool = False,
               base_serving=None, roles=None,
               prefill_dwell_per_token: float = 0.0):
    """One wall-clock fleet row: ``n_workers`` REAL ``serving.worker``
    child processes, dialed over sockets, replaying ``trace`` against
    ``time.monotonic``. ``shed=True`` arms deadline shedding with every
    request due ``_FLEET_SLO`` after submission (the overload-accounting
    row). ``roles`` pins ``serving.role`` per worker (the disagg rows);
    ``prefill_dwell_per_token`` arms the worker's prefill-proportional
    dwell on EVERY worker, so a role split changes where prefill cost
    lands, never how much of it exists."""
    import subprocess

    from distributeddeeplearning_tpu.cli import read_worker_ready
    from distributeddeeplearning_tpu.config import ServingConfig
    from distributeddeeplearning_tpu.serving import Request, RequestShed
    from distributeddeeplearning_tpu.serving.router import connect_fleet

    extra = (dict(shed_policy="deadline", shed_percentile=50.0)
             if shed else None)
    spec = _fleet_spec(extra, base=base_serving)
    cfg = ServingConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in spec["serving"].items()
    })
    procs, endpoints = [], []
    for i in range(n_workers):
        wspec = spec if roles is None else _fleet_spec(
            {**(extra or {}), "role": roles[i]}, base=base_serving
        )
        cmd = [sys.executable, "-m",
               "distributeddeeplearning_tpu.serving.worker",
               "--spec-json", json.dumps(wspec), "--seed", str(_SEED),
               "--replica-index", str(i),
               "--dwell-s", str(_FLEET_DWELL)]
        if prefill_dwell_per_token:
            cmd += ["--prefill-dwell-per-token-s",
                    str(prefill_dwell_per_token)]
        if telemetry_dir:
            cmd += ["--telemetry-dir", telemetry_dir]
        env = dict(os.environ)
        env["DDL_PROCESS_INDEX"] = str(i)
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True,
        ))
    worker_rcs = []
    try:
        for p in procs:
            ready = read_worker_ready(p.stdout)
            endpoints.append((ready["host"], ready["port"]))
        router = connect_fleet(cfg, endpoints)
        compiles_ready = [r.num_compiles for r in router.replicas]
        shed_n = 0
        i = 0
        t0 = time.monotonic()
        while i < len(trace) or not router.idle:
            now = time.monotonic() - t0
            while i < len(trace) and trace[i][0] <= now:
                _, prompt, max_new = trace[i]
                try:
                    router.submit(Request(
                        prompt=list(prompt), max_new_tokens=max_new,
                        request_id=i,
                        deadline_s=(time.monotonic() + _FLEET_SLO
                                    if shed else None),
                    ))
                except RequestShed:
                    shed_n += 1
                i += 1
            busy = router.step()
            if not busy and i < len(trace):
                # Fleet idle, next arrival not yet due: sleep toward it
                # instead of spinning the submit loop hot.
                time.sleep(min(0.002, max(
                    0.0, trace[i][0] - (time.monotonic() - t0))))
        makespan = max(time.monotonic() - t0, 1e-9)
        finished = router.finished()
        dropped = sum(r.dropped_count for r in router.replicas)
        stats = router.stats()
        router.shutdown_fleet()
        worker_rcs = [p.wait(timeout=60) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
    served_tokens = sum(len(s.generated) for s in finished)
    ttft = [s.first_token_s - s.arrival_s for s in finished
            if s.first_token_s is not None]
    # Decode-phase inter-token latency: gaps BETWEEN a request's own
    # generated tokens, pooled across requests. TTFT (arrival -> first
    # token, which carries queueing + prefill + any handoff hop) is
    # deliberately excluded — this is the column disaggregation moves.
    itl = [b - a for s in finished
           for a, b in zip(s.token_times_s, s.token_times_s[1:])]
    # Per-worker compile pin over the wire: the heartbeat-propagated
    # count must still equal the at-ready count — the whole run added
    # zero compiles in any worker process. With the prefix cache on,
    # the suffix buckets join each worker's warmed executable set.
    pin = len(spec["serving"]["prompt_buckets"]) + 1
    if spec["serving"].get("prefix_cache"):
        pin += len(spec["serving"].get("suffix_buckets") or ())
    compiles_now = [r.num_compiles for r in router.replicas]
    return {
        "workers": n_workers,
        "roles": list(roles) if roles else ["unified"] * n_workers,
        "transport": "socket",
        "dwell_s": _FLEET_DWELL,
        "prefill_dwell_per_token_s": prefill_dwell_per_token,
        "requests": len(trace),
        "served": len(finished),
        "shed": shed_n,
        "dropped_in_queue": dropped,
        "served_tokens": served_tokens,
        "wall_makespan_s": round(makespan, 4),
        "wallclock_tokens_per_sec": round(served_tokens / makespan, 2),
        "ttft_s": _exact_pcts(ttft),
        "decode_itl_s": _exact_pcts(itl),
        "shed_policy": "deadline" if shed else "off",
        "slo_s": _FLEET_SLO if shed else None,
        "tokens_match_oracle": all(
            list(s.generated) == ref_tokens[s.request.request_id]
            for s in finished
        ),
        "compiles_at_ready": compiles_ready,
        "compiles_after_run": compiles_now,
        "compile_pin_per_worker": pin,
        "rerouted": stats["rerouted"],
        "failed": stats["failed"],
        "handoffs": stats.get("handoffs", 0),
        "handoff_parts": stats.get("handoff_parts", 0),
        "worker_exit_codes": worker_rcs,
    }


def main() -> int:
    import numpy as np

    import jax
    from distributeddeeplearning_tpu import models

    trace = _make_trace(_SEED, _RATE)
    model = models.get_model("gpt2", **_MODEL_KW)
    probe = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(_SEED), probe)["params"]

    spec = f"ngram:{_SPEC_K}"
    rows = [
        _run_mode(model, params, trace, static=False),
        _run_mode(model, params, trace, static=True),
        _run_mode(model, params, trace, static=False, kernel="pallas"),
        # Speculation on the ADVERSARIAL (random-byte) trace: parity and
        # honest accept-rate where prompt-lookup drafting is hardest.
        _run_mode(model, params, trace, static=False, speculation=spec),
    ]
    if _QUANT_ROW:
        rows.append(_run_mode(model, params, trace, static=False,
                              quant="int8"))
    cont, stat, pallas, spec_adv = rows[0], rows[1], rows[2], rows[3]
    # The repetitive-text workload: speculative on/off, same trace.
    rep_trace = _make_repetitive_trace(_SEED + 1)
    rep_off = _run_mode(model, params, rep_trace, static=False)
    rep_on = _run_mode(model, params, rep_trace, static=False,
                       speculation=spec)
    # The router scale-out sweep: one trace per load multiplier (same
    # seed -> same prompts, compressed arrivals), every (load, replicas)
    # pair a row. The parity oracle is a single direct-engine run — the
    # prompts are rate-invariant, so one oracle covers every load.
    ref_tokens = _reference_tokens(
        model, params, _make_trace(_SEED, _RATE, n=_ROUTER_N)
    )
    router_rows = []
    for load in _LOADS:
        rtrace = _make_trace(_SEED, _RATE * load, n=_ROUTER_N)
        for n in _REPLICAS:
            router_rows.append(_run_router(
                model, params, rtrace, replicas=n, load_x=load,
                trace_seed=_SEED, ref_tokens=ref_tokens,
            ))
    by_cell = {(r["replicas"], r["load_x"]): r for r in router_rows}

    def _goodput_ratio(n, load):
        a, b = by_cell.get((n, load)), by_cell.get((1, load))
        if a is None or b is None:
            return None
        return round(
            a["goodput_tokens_per_sec"] / b["goodput_tokens_per_sec"], 3
        )

    shed_100x = by_cell.get((1, 100.0))
    shed_rows = [r for r in router_rows if r["shed"] or
                 r["dropped_in_queue"]]
    router_block = {
        "timebase": (
            "virtual: N parallel chips simulated by per-replica virtual "
            "clocks charged with measured host step time; goodput = "
            "served tokens / virtual makespan"
        ),
        "slo_s": _SLO_S,
        "replicas_swept": list(_REPLICAS),
        "loads_swept": list(_LOADS),
        "trace_seed": _SEED,
        "rows": router_rows,
        "comparison": {
            # THE scale-out headline (acceptance bar >= 3.0 on the full
            # sweep): fleet goodput, 4 replicas over 1, at 10x load.
            "goodput_ratio_4x_at_10x": _goodput_ratio(4, 10.0),
            "goodput_ratio_2x_at_10x": _goodput_ratio(2, 10.0),
            "goodput_ratio_4x_at_100x": _goodput_ratio(4, 100.0),
            # SLO admission control under overload: the single replica
            # at 100x must actually shed (typed rejections, no prefill
            # spent), not just queue and time out.
            "shed_rate_100x_1_replica": (
                None if shed_100x is None else shed_100x["shed_rate"]
            ),
            "tokens_match_reference": all(
                r["tokens_match_reference"] for r in router_rows
            ),
            "zero_recompiles_per_replica": all(
                r["compiles_after_run"] == r["compiles_warmup"]
                == r["compile_pin"] for r in router_rows
            ),
            # Served requests' p99 TTFT stays bounded near the SLO even
            # on rows that shed/dropped — admission control converts
            # overload into rejections, not unbounded latency.
            "p99_ttft_bounded_under_shedding": bool(shed_rows) and all(
                r["ttft_exact_s"]["p99"] is not None
                and r["ttft_exact_s"]["p99"] <= _SLO_S * 1.5
                for r in shed_rows
            ),
        },
    }
    # The prefix-cache block: shared-prefix trace cache on/off + the
    # adversarial (random-byte, every prompt unique) control cache-on.
    px_trace = _make_shared_prefix_trace(_SEED + 2)
    px_on = _run_mode(model, params, px_trace, static=False,
                      serving_kw=_PX_SERVING_KW)
    px_off = _run_mode(model, params, px_trace, static=False,
                       serving_kw=_PX_SERVING_OFF)
    # The adversarial control reuses the wall rows' trace; its prompts
    # (4..31 tokens) never select the 64 bucket, so the reference row
    # `cont` is the exact cache-off oracle for its checksum.
    adv_on = _run_mode(model, params, trace, static=False,
                       serving_kw=_PX_SERVING_KW)
    px_pin = (len(_PX_SERVING_KW["prompt_buckets"])
              + len(_PX_SERVING_KW["suffix_buckets"]) + 1)
    prefix_block = {
        "workload": {
            "prefixes": _PX_PREFIXES,
            "prefix_len": _PX_PREFIX_LEN,
            "suffix_len_range": list(_PX_SUFFIX_LEN),
            "max_new_range": list(_MAX_NEW),
            "requests": _N, "rate_req_per_s": _RATE, "seed": _SEED + 2,
        },
        "serving": {k: list(v) if isinstance(v, tuple) else v
                    for k, v in _PX_SERVING_KW.items()},
        "rows": [px_on, px_off, adv_on],
        "comparison": {
            # THE prefix-cache headline (acceptance bar >= 2.0): prompt
            # tokens the trace carries over prompt tokens the cache-on
            # engine actually prefilled (= trie misses) — what suffix-
            # only prefill removed from the critical path.
            "prefill_token_reduction_shared": round(
                px_on["prompt_tokens"] / px_on["prefix"]["miss_tokens"], 3
            ),
            "shared_hit_rate": px_on["prefix"]["hit_rate"],
            # Warm admissions prefill an 8-wide suffix instead of a
            # 64-wide prompt: first tokens arrive sooner under the SAME
            # trace and clock.
            "p50_ttft_ratio_shared": round(
                px_on["ttft_exact_s"]["p50"]
                / px_off["ttft_exact_s"]["p50"], 3
            ),
            "p50_ttft_improved_shared":
                px_on["ttft_exact_s"]["p50"] < px_off["ttft_exact_s"]["p50"],
            # Reuse changes WHERE KV comes from, never the tokens.
            "tokens_match_cache_off_shared":
                px_on["token_checksum"] == px_off["token_checksum"],
            "tokens_match_reference_adversarial":
                adv_on["token_checksum"] == cont["token_checksum"],
            # Honest control: unique prompts -> the trie absorbs nothing.
            "adversarial_hit_rate": adv_on["prefix"]["hit_rate"],
            # Compile pin: suffix widths join the shared prefill
            # executable set — len(prompt_buckets) + len(suffix_buckets)
            # + 1, warmup-only, zero steady-state recompiles on every
            # row including the warm one.
            "compile_pin": px_pin,
            "zero_recompiles_with_cache": (
                all(r["compiles_after_run"] == r["compiles_warmup"]
                    for r in (px_on, px_off, adv_on))
                and px_on["compiles_warmup"] == px_pin
                and adv_on["compiles_warmup"] == px_pin
            ),
        },
    }
    # The kv_hierarchy block: the shared-prefix workload at 8 prefixes on
    # a device pool constrained too small to cache them, spill off / fp /
    # fp-tight / int8, plus the int8 adversarial control (the random-byte
    # trace, same constrained pool) and the measured int8 logit probe.
    kv_trace = _make_kv_trace(_SEED + 3)
    kv_kw_fp = {**_PX_SERVING_KW, "spill_blocks": _SPILL_BLOCKS}
    kv_kw_tight = {**_PX_SERVING_KW, "spill_blocks": _KV_TIGHT_BLOCKS}
    kv_kw_int8 = {**kv_kw_fp, "spill_codec": "int8"}
    kv_off = _run_mode(model, params, kv_trace, static=False,
                       serving_kw=_PX_SERVING_KW,
                       constrain_blocks=_KV_DEVICE_BLOCKS)
    kv_fp = _run_mode(model, params, kv_trace, static=False,
                      serving_kw=kv_kw_fp,
                      constrain_blocks=_KV_DEVICE_BLOCKS)
    kv_tight = _run_mode(model, params, kv_trace, static=False,
                         serving_kw=kv_kw_tight,
                         constrain_blocks=_KV_DEVICE_BLOCKS)
    kv_int8 = _run_mode(model, params, kv_trace, static=False,
                        serving_kw=kv_kw_int8,
                        constrain_blocks=_KV_DEVICE_BLOCKS)
    kv_adv = _run_mode(model, params, trace, static=False,
                       serving_kw=kv_kw_int8,
                       constrain_blocks=_KV_DEVICE_BLOCKS)
    # The async-promote A/B (ROADMAP 2b): the fp spill row re-run with
    # promote_async=False — same trace, same pool, same programs; only
    # WHEN the H2D upload happens moves. promote_wait (host stall at
    # prefill dispatch) is the pinned column.
    kv_sync = _run_mode(model, params, kv_trace, static=False,
                        serving_kw=kv_kw_fp,
                        constrain_blocks=_KV_DEVICE_BLOCKS,
                        promote_async=False)
    kv_probe = _int8_promote_probe(model, params)
    kv_rows = [kv_off, kv_fp, kv_tight, kv_int8, kv_adv, kv_sync]
    kv_block = {
        "workload": {
            "prefixes": _KV_PREFIXES,
            "prefix_len": _PX_PREFIX_LEN,
            "suffix_len_range": list(_PX_SUFFIX_LEN),
            "max_new_range": list(_MAX_NEW),
            "requests": _KV_N, "rate_req_per_s": _RATE,
            "seed": _SEED + 3,
        },
        "device_blocks": _KV_DEVICE_BLOCKS,
        "spill_blocks": _SPILL_BLOCKS,
        "tight_spill_blocks": _KV_TIGHT_BLOCKS,
        "rows": kv_rows,
        "comparison": {
            # THE memory-hierarchy headline (acceptance bar >= 2.0):
            # prefix hit tokens the spill tier recovers over what the
            # same constrained device pool retains on its own.
            "hit_token_recovery_spill_fp": round(
                kv_fp["prefix"]["hit_tokens"]
                / max(kv_off["prefix"]["hit_tokens"], 1), 3
            ),
            "hit_tokens_spill_off": kv_off["prefix"]["hit_tokens"],
            "hit_tokens_spill_fp": kv_fp["prefix"]["hit_tokens"],
            "hit_tokens_host_spill_fp":
                kv_fp["prefix"]["hit_tokens_host"],
            "promotes_spill_fp": kv_fp["prefix"]["promotes"],
            "spills_spill_fp": kv_fp["prefix"]["spills"],
            # Async promote (ROADMAP 2b): staging the promoted chain's
            # upload at admission leaves only the pool scatter on the
            # prefill-dispatch path; the sync baseline pays the pop +
            # device_put there too. On the CPU sim device_put is a
            # near-zero-copy dispatch, so the pin is a REGRESSION bar
            # (async must not add dispatch-path cost; 1.5x covers
            # scheduler jitter at ~ms scale on a shared host) plus the
            # structural claim that staging actually ran off the
            # dispatch path — the overlap win itself is an accelerator
            # property. Parity rides along: WHEN the upload happens can
            # never change the tokens.
            "promote_wait_p50_ms_async":
                (kv_fp["promote_wait_ms"] or {}).get("p50"),
            "promote_wait_p50_ms_sync":
                (kv_sync["promote_wait_ms"] or {}).get("p50"),
            "promote_stage_p50_ms_async":
                (kv_fp["promote_stage_ms"] or {}).get("p50"),
            "async_promote_p50_no_worse": (
                kv_fp["promote_wait_ms"] is not None
                and kv_sync["promote_wait_ms"] is not None
                and kv_fp["promote_wait_ms"]["p50"]
                <= 1.5 * kv_sync["promote_wait_ms"]["p50"]
            ),
            "async_promote_staged_off_dispatch_path": (
                kv_fp["promote_stage_ms"] is not None
                and kv_fp["promote_async"] is True
                and kv_sync["promote_async"] is False
            ),
            "tokens_match_spill_off_sync_promote":
                kv_sync["token_checksum"] == kv_off["token_checksum"],
            # fp payloads are bitwise: the hierarchy changes WHERE KV
            # waits, never the tokens — including when the tight budget
            # final-evicts mid-trace and prefixes drop back to cold.
            "tokens_match_spill_off":
                kv_fp["token_checksum"] == kv_off["token_checksum"],
            "tokens_match_spill_off_tight":
                kv_tight["token_checksum"] == kv_off["token_checksum"],
            "final_evictions_under_tight_budget":
                kv_tight["prefix"]["final_evictions"],
            "int8_promotes": kv_int8["prefix"]["promotes"],
            "int8_hit_tokens": kv_int8["prefix"]["hit_tokens"],
            # Honest control: unique random prompts, constrained pool,
            # int8 codec armed — nothing ever matches, so nothing is
            # promoted and no request's logits touch quantized KV.
            "int8_adversarial_hit_rate": kv_adv["prefix"]["hit_rate"],
            "int8_logit_probe": kv_probe,
            # Spill/promote are eager host transfers, not programs: the
            # prefix compile pin is unchanged on every row.
            "compile_pin": px_pin,
            "zero_recompiles_with_spill": all(
                r["compiles_after_run"] == r["compiles_warmup"] == px_pin
                for r in kv_rows
            ),
        },
    }
    # The kv_quant block: the SAME traces and constrained pool with the
    # device pool itself quantized (serving.kv_quant='int8'). The off
    # rows are reused, not rerun: `cont` is the fp oracle for the
    # standard trace and `kv_off` for the constrained shared-prefix
    # trace — same seeds, same compiled programs.
    q_kw = {**_PX_SERVING_KW, "kv_quant": "int8"}
    q_kw_spill = {**kv_kw_fp, "kv_quant": "int8"}
    q_std = _run_mode(model, params, trace, static=False,
                      serving_kw={**_SERVING_KW, "kv_quant": "int8"})
    q_int8 = _run_mode(model, params, kv_trace, static=False,
                       serving_kw=q_kw,
                       constrain_blocks=_KV_DEVICE_BLOCKS)
    q_spill = _run_mode(model, params, kv_trace, static=False,
                        serving_kw=q_kw_spill,
                        constrain_blocks=_KV_DEVICE_BLOCKS)
    q_adv = _run_mode(model, params, trace, static=False,
                      serving_kw=q_kw_spill,
                      constrain_blocks=_KV_DEVICE_BLOCKS)
    q_probe = _kv_quant_drift_probe(model, params)
    q_rows = [q_std, q_int8, q_spill, q_adv]
    base_pin = len(_SERVING_KW["prompt_buckets"]) + 1
    kvq_block = {
        "workload": {
            "standard_trace_seed": _SEED,
            "shared_prefix_trace_seed": _SEED + 3,
            "prefixes": _KV_PREFIXES,
            "prefix_len": _PX_PREFIX_LEN,
        },
        "device_blocks": _KV_DEVICE_BLOCKS,
        "spill_blocks": _SPILL_BLOCKS,
        "rows": q_rows,
        "comparison": {
            # THE capacity headline (acceptance bar >= 2.0): budget-
            # minted pool blocks, int8 pool over fp pool, at the SAME
            # hbm_budget_mb (measured ~3-4x: scales cost 4/D per slot).
            "block_capacity_ratio_int8": round(
                q_int8["num_blocks"] / kv_off["num_blocks"], 3
            ),
            "num_blocks_fp": kv_off["num_blocks"],
            "num_blocks_int8": q_int8["num_blocks"],
            "kv_bytes_per_token_fp": kv_off["kv_bytes_per_token"],
            "kv_bytes_per_token_int8": q_int8["kv_bytes_per_token"],
            # Greedy parity on the standard random-byte trace: per-slot
            # int8 KV does not change the tokens there (the engine test
            # pins this on two architectures; the artifact carries it).
            "tokens_match_fp_reference":
                q_std["token_checksum"] == cont["token_checksum"],
            # Parity on the constrained shared-prefix trace too: reused
            # quantized prefixes feed every warm request's logits.
            "tokens_match_fp_shared":
                q_int8["token_checksum"] == kv_off["token_checksum"],
            # The hierarchy composes on top: int8 device blocks demote/
            # promote bitwise through the fp codec, recovering hit
            # tokens the constrained int8 pool alone evicts.
            "spill_hit_token_recovery_int8": round(
                q_spill["prefix"]["hit_tokens"]
                / max(q_int8["prefix"]["hit_tokens"], 1), 3
            ),
            "hit_tokens_int8": q_int8["prefix"]["hit_tokens"],
            "hit_tokens_int8_spill": q_spill["prefix"]["hit_tokens"],
            "promotes_int8_spill": q_spill["prefix"]["promotes"],
            # Honest control: unique random prompts -> nothing reuses
            # quantized KV, and the trie says so exactly.
            "adversarial_hit_rate": q_adv["prefix"]["hit_rate"],
            # The read-path drift, measured: suffix prefill gathering a
            # cached prefix from the int8 pool vs the fp pool.
            "logit_drift_probe": q_probe,
            # Quantized scatter/gather are baked into the SAME programs:
            # both compile pins unchanged, zero steady-state recompiles.
            "compile_pin_standard": base_pin,
            "compile_pin_prefix": px_pin,
            "zero_recompiles_with_kv_quant": (
                all(r["compiles_after_run"] == r["compiles_warmup"]
                    for r in q_rows)
                and q_std["compiles_warmup"] == base_pin
                and all(r["compiles_warmup"] == px_pin
                        for r in (q_int8, q_spill, q_adv))
            ),
        },
    }
    # The socket-fleet block: REAL serving.worker child processes behind
    # real sockets, replayed against time.monotonic — the only block in
    # this artifact measured on the wall clock instead of a virtual
    # clock. Each worker sleeps `dwell_s` per engine step (the CPU sim's
    # stand-in for device latency on a 1-core host), which makes the
    # workload latency-bound so process overlap yields genuine
    # wall-clock scale-out. The oracle is a direct single-engine run of
    # the same request list in a subprocess built from the same spec.
    import tempfile

    from distributeddeeplearning_tpu.telemetry_aggregate import (
        build_fleet,
    )

    fleet_rows = []
    fleet_merge_processes = None
    if _FLEET_SIZES:
        fleet_trace = _make_trace(_SEED + 4, _FLEET_RATE, n=_FLEET_N)
        fleet_ref = _fleet_oracle_tokens(fleet_trace)
    for n in _FLEET_SIZES:
        if n == max(_FLEET_SIZES):
            # The largest row also exercises the merged-telemetry path:
            # each worker stamps process_index=i, and build_fleet folds
            # the stamped artifacts into one FLEET.json.
            with tempfile.TemporaryDirectory() as tdir:
                row = _run_fleet(n, fleet_trace, fleet_ref,
                                 telemetry_dir=tdir)
                fleet_merge_processes = build_fleet(
                    tdir, write=False
                )["processes"]
        else:
            row = _run_fleet(n, fleet_trace, fleet_ref)
        fleet_rows.append(row)
    # The overload-accounting row: one worker, deadline shedding armed,
    # every request due _FLEET_SLO after submission. served + shed +
    # dropped must account for every request exactly. The worker runs
    # with telemetry ON: the router's deadline estimate is driven by the
    # heartbeat-pushed queue-wait/prefill percentiles, which come from
    # the worker's telemetry histograms — a bare worker pushes zeros and
    # every infeasible request ends as a worker-side queue drop instead
    # of a router-side typed shed.
    if _FLEET_SIZES:
        with tempfile.TemporaryDirectory() as shed_tdir:
            fleet_shed = _run_fleet(1, fleet_trace, fleet_ref,
                                    shed=True, telemetry_dir=shed_tdir)
    else:
        fleet_shed = None
    fleet_by_n = {r["workers"]: r for r in fleet_rows}

    def _fleet_tps_ratio(n):
        a, b = fleet_by_n.get(n), fleet_by_n.get(1)
        if a is None or b is None:
            return None
        return round(a["wallclock_tokens_per_sec"]
                     / b["wallclock_tokens_per_sec"], 3)

    fleet_block = None if not _FLEET_SIZES else {
        "timebase": (
            "wall clock: real child worker processes behind real "
            "sockets, arrivals replayed against time.monotonic; "
            "tokens/s = served tokens / wall makespan. Each worker "
            "sleeps dwell_s per engine step as the CPU sim's "
            "device-latency stand-in (1-core host: the workload must "
            "be latency-bound for process overlap to show as "
            "wall-clock scale-out)."
        ),
        "dwell_s": _FLEET_DWELL,
        "workers_swept": list(_FLEET_SIZES),
        "requests": _FLEET_N,
        "rate_req_per_s": _FLEET_RATE,
        "trace_seed": _SEED + 4,
        "serving": {k: list(v) if isinstance(v, tuple) else v
                    for k, v in _FLEET_SERVING_KW.items()},
        "rows": fleet_rows,
        "shed_row": fleet_shed,
        "comparison": {
            # THE fleet headline (acceptance bar >= 2.5): wall-clock
            # tokens/s, 4 socket workers over 1, at saturating load.
            "wallclock_tps_ratio_4x": _fleet_tps_ratio(4),
            "wallclock_tps_ratio_2x": _fleet_tps_ratio(2),
            # Exact greedy parity vs the direct single-engine oracle,
            # on every fleet size.
            "tokens_match_oracle": all(
                r["tokens_match_oracle"] for r in fleet_rows
            ),
            # Per-worker compile pin over the wire: heartbeat-carried
            # num_compiles never moves after worker_ready.
            "zero_recompiles_per_worker": all(
                r["compiles_after_run"] == r["compiles_at_ready"]
                == [r["compile_pin_per_worker"]] * r["workers"]
                for r in fleet_rows
            ),
            # Overload accounting: typed sheds + queue drops + served
            # cover the trace exactly; nothing vanishes.
            "shed_accounting_exact": (
                fleet_shed["served"] + fleet_shed["shed"]
                + fleet_shed["dropped_in_queue"]
                == fleet_shed["requests"]
            ),
            "shed_count_overload": fleet_shed["shed"],
            # cli report's merge surface: the stamped per-worker
            # telemetry folds into one FLEET.json whose process list is
            # exactly the worker indices.
            "fleet_merge_processes": fleet_merge_processes,
            "workers_exit_zero": all(
                all(rc == 0 for rc in r["worker_exit_codes"])
                for r in fleet_rows + [fleet_shed]
            ),
        },
    }
    # The disagg block: same worker count, same trace, same oracle —
    # only the topology moves. Unified row first (it is the baseline
    # the headline divides by).
    disagg_block = None
    if _FLEET_SIZES and _DISAGG_ON:
        d_trace = _make_disagg_trace(_SEED + 5)
        d_ref = _fleet_oracle_tokens(d_trace, base=_DISAGG_SERVING_KW)
        d_roles = (["prefill"]
                   + ["decode"] * (_DISAGG_WORKERS - 1))
        d_uni = _run_fleet(
            _DISAGG_WORKERS, d_trace, d_ref,
            base_serving=_DISAGG_SERVING_KW,
            prefill_dwell_per_token=_DISAGG_PREFILL_DWELL,
        )
        d_split = _run_fleet(
            _DISAGG_WORKERS, d_trace, d_ref,
            base_serving=_DISAGG_SERVING_KW, roles=d_roles,
            prefill_dwell_per_token=_DISAGG_PREFILL_DWELL,
        )
        itl_uni = d_uni["decode_itl_s"]["p99"]
        itl_split = d_split["decode_itl_s"]["p99"]
        disagg_block = {
            "timebase": (
                "wall clock: real child worker processes behind real "
                "sockets (the fleet block's machinery) plus a per-"
                "prefilled-token dwell on EVERY worker of both fleets "
                "— prefill cost grows with uncached prompt length "
                "while a decode step stays flat, so the A/B measures "
                "where prefill interference lands, not an assumed "
                "speedup."
            ),
            "workers": _DISAGG_WORKERS,
            "roles_split": d_roles,
            "requests": _DISAGG_N,
            "rate_req_per_s": _DISAGG_RATE,
            "prompt_len_range": list(_DISAGG_PROMPT_LEN),
            "max_new_range": list(_DISAGG_MAX_NEW),
            "trace_seed": _SEED + 5,
            "dwell_s": _FLEET_DWELL,
            "prefill_dwell_per_token_s": _DISAGG_PREFILL_DWELL,
            "serving": {k: list(v) if isinstance(v, tuple) else v
                        for k, v in _DISAGG_SERVING_KW.items()},
            "rows": [d_uni, d_split],
            "comparison": {
                # THE disaggregation headline (acceptance bar <= 0.6):
                # decode-phase p99 inter-token latency, role-split
                # fleet over the same-size unified fleet, long-prompt
                # burst. Decode-role lanes never stall a full prompt's
                # prefill between two of their own tokens.
                "decode_p99_itl_ratio": (
                    None if not itl_uni or itl_split is None
                    else round(itl_split / itl_uni, 3)
                ),
                "decode_p99_itl_s_unified": itl_uni,
                "decode_p99_itl_s_split": itl_split,
                "decode_p50_itl_s_unified": d_uni["decode_itl_s"]["p50"],
                "decode_p50_itl_s_split": d_split["decode_itl_s"]["p50"],
                # Exact greedy parity vs the single-engine oracle on
                # BOTH topologies: the handoff re-samples from the same
                # per-request rng chain over the same logits.
                "tokens_match_oracle": (
                    d_uni["tokens_match_oracle"]
                    and d_split["tokens_match_oracle"]
                ),
                # Per-role compile pins unchanged: prefill and decode
                # workers warm the same executable set; the role split
                # adds no programs.
                "zero_recompiles_per_worker": all(
                    r["compiles_after_run"] == r["compiles_at_ready"]
                    == [r["compile_pin_per_worker"]] * r["workers"]
                    for r in (d_uni, d_split)
                ),
                # Conservation: served + shed + dropped covers the
                # trace exactly on both topologies — a handed-off
                # request is still exactly one request.
                "accounting_exact": all(
                    r["served"] + r["shed"] + r["dropped_in_queue"]
                    == r["requests"] for r in (d_uni, d_split)
                ),
                # Every request crossed the split exactly once; the
                # unified fleet never manufactured a handoff.
                "handoffs_split": d_split["handoffs"],
                "handoffs_cover_trace":
                    d_split["handoffs"] == _DISAGG_N,
                "handoffs_unified_zero": d_uni["handoffs"] == 0,
                "workers_exit_zero": all(
                    all(rc == 0 for rc in r["worker_exit_codes"])
                    for r in (d_uni, d_split)
                ),
            },
        }
    record = {
        "benchmark": "serving",
        "workload": {
            "model": "gpt2", **_MODEL_KW, "serving": dict(_SERVING_KW),
            "requests": _N, "rate_req_per_s": _RATE, "seed": _SEED,
            "trace_seed": _SEED,
            "prompt_len_range": list(_PROMPT_LEN),
            "max_new_range": list(_MAX_NEW),
        },
        "platform": jax.devices()[0].platform,
        "rows": rows,
        "router": router_block,
        "fleet": fleet_block,
        "disagg": disagg_block,
        "prefix_cache": prefix_block,
        "kv_hierarchy": kv_block,
        "kv_quant": kvq_block,
        "speculation": {
            "k": _SPEC_K,
            "workload": {
                "pattern_period_range": list(_REP_PATTERN),
                "prompt_len_range": list(_REP_PROMPT_LEN),
                "max_new_range": list(_REP_MAX_NEW),
                "requests": _N, "rate_req_per_s": _REP_RATE,
                "seed": _SEED + 1,
            },
            "rows": [rep_off, rep_on],
            "comparison": {
                # THE speculation headline (acceptance bar >= 1.25 on the
                # full-load artifact): decode-phase tokens/s, speculative
                # over non-speculative, on the repetitive-text trace.
                "spec_decode_tps_ratio": round(
                    rep_on["decode_tokens_per_sec"]
                    / rep_off["decode_tokens_per_sec"], 3
                ),
                "spec_tokens_match_non_speculative":
                    rep_on["token_checksum"] == rep_off["token_checksum"],
                "spec_accept_rate_repetitive": rep_on["accept_rate"],
                "spec_mean_accepted_per_step":
                    rep_on["mean_accepted_per_step"],
            },
        },
        "comparison": {
            "throughput_ratio": round(
                cont["tokens_per_sec"] / stat["tokens_per_sec"], 3
            ),
            "p99_ttft_ratio": round(
                cont["ttft_s"]["p99"] / stat["ttft_s"]["p99"], 3
            ),
            # The artifact-pinned claims (tests/test_serving_bench.py):
            "continuous_beats_static_throughput":
                cont["tokens_per_sec"] > stat["tokens_per_sec"],
            "continuous_p99_ttft_no_worse":
                cont["ttft_s"]["p99"] <= stat["ttft_s"]["p99"],
            "zero_recompiles_in_steady_state": all(
                r["compiles_after_run"] == r["compiles_warmup"]
                for r in rows
            ),
            # The hot-path claims (PR 11): the pallas read path changes
            # WHERE the pool is read from, never the tokens; and the
            # decode executable aliases its cache in place.
            "pallas_tokens_match_reference":
                pallas["token_checksum"] == cont["token_checksum"],
            # Speculation parity on the ADVERSARIAL trace: drafting may
            # buy little here (honest accept rate rides along, even when
            # the ratio is < 1), but the tokens must never change.
            "speculative_tokens_match_reference":
                spec_adv["token_checksum"] == cont["token_checksum"],
            "speculative_accept_rate_adversarial": spec_adv["accept_rate"],
            "decode_donation_live": all(
                r["decode_donated_args"] > 0 for r in rows
            ),
            # The histogram pin (docs/OBSERVABILITY.md): every row's
            # streaming-histogram TTFT percentiles agree with the exact
            # sorted-sample values within one bucket's relative width.
            "hist_percentiles_within_bucket_error": all(
                r["ttft_hist_vs_exact"]["ok"] for r in rows
            ),
        },
    }
    with open(_OUT, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(json.dumps(record["comparison"], indent=2))
    print(json.dumps(record["speculation"]["comparison"], indent=2))
    print(json.dumps(record["router"]["comparison"], indent=2))
    if fleet_block is not None:
        print(json.dumps(record["fleet"]["comparison"], indent=2))
    if disagg_block is not None:
        print(json.dumps(record["disagg"]["comparison"], indent=2))
    print(json.dumps(record["prefix_cache"]["comparison"], indent=2))
    print(json.dumps(record["kv_hierarchy"]["comparison"], indent=2))
    print(json.dumps(record["kv_quant"]["comparison"], indent=2))
    print(f"wrote {_OUT}")
    return 0


def check(path: str = _OUT) -> int:
    """Validate an EXISTING artifact's pinned claims without re-running
    the engines — the cheap CI gate after regeneration. Exits non-zero
    listing every failed claim."""
    with open(path) as f:
        record = json.load(f)
    comp = record.get("comparison", {})
    spec = record.get("speculation", {})
    spec_comp = spec.get("comparison", {})
    failures = []

    def claim(name, ok):
        if not ok:
            failures.append(name)

    for key in ("continuous_beats_static_throughput",
                "continuous_p99_ttft_no_worse",
                "zero_recompiles_in_steady_state",
                "pallas_tokens_match_reference",
                "speculative_tokens_match_reference",
                "decode_donation_live",
                "hist_percentiles_within_bucket_error"):
        claim(key, comp.get(key) is True)
    claim("throughput_ratio > 1",
          (comp.get("throughput_ratio") or 0) > 1.0)
    # The speculation headline: >= 1.25x decode-phase tokens/s on the
    # repetitive-text workload, with exact token parity there too.
    claim("spec_decode_tps_ratio >= 1.25",
          (spec_comp.get("spec_decode_tps_ratio") or 0) >= 1.25)
    claim("spec_tokens_match_non_speculative",
          spec_comp.get("spec_tokens_match_non_speculative") is True)
    rate = spec_comp.get("spec_accept_rate_repetitive")
    claim("spec_accept_rate_repetitive in (0, 1]",
          rate is not None and 0.0 < rate <= 1.0)
    adv = comp.get("speculative_accept_rate_adversarial")
    claim("speculative_accept_rate_adversarial in [0, 1]",
          adv is not None and 0.0 <= adv <= 1.0)
    rows = record.get("rows", [])
    claim("four benchmark rows present", len(rows) >= 4)
    claim("speculative row flagged",
          any(r.get("speculation", "off") != "off" for r in rows))
    # Router scale-out claims (the full-sweep artifact; a shrunken
    # smoke sweep writes None for missing cells and fails here — the
    # COMMITTED file must carry the complete sweep).
    rcomp = record.get("router", {}).get("comparison", {})
    claim("router_goodput_ratio_4x_at_10x >= 3.0",
          (rcomp.get("goodput_ratio_4x_at_10x") or 0) >= 3.0)
    claim("router_tokens_match_reference",
          rcomp.get("tokens_match_reference") is True)
    claim("router_zero_recompiles_per_replica",
          rcomp.get("zero_recompiles_per_replica") is True)
    claim("router_shed_rate_100x_1_replica > 0",
          (rcomp.get("shed_rate_100x_1_replica") or 0) > 0)
    claim("router_p99_ttft_bounded_under_shedding",
          rcomp.get("p99_ttft_bounded_under_shedding") is True)
    # Socket-fleet claims (wall-clock, real child processes): >= 2.5x
    # tokens/s at 4 workers over 1 at saturating load, exact greedy
    # parity vs the direct single-engine oracle, per-worker compile
    # pins unchanged over the wire, exact shed accounting under
    # overload, and the stamped telemetry merging into one FLEET.json
    # whose process list is exactly the worker indices.
    fcomp = (record.get("fleet") or {}).get("comparison", {})
    claim("fleet_wallclock_tps_ratio_4x >= 2.5",
          (fcomp.get("wallclock_tps_ratio_4x") or 0) >= 2.5)
    claim("fleet_tokens_match_oracle",
          fcomp.get("tokens_match_oracle") is True)
    claim("fleet_zero_recompiles_per_worker",
          fcomp.get("zero_recompiles_per_worker") is True)
    claim("fleet_shed_accounting_exact",
          fcomp.get("shed_accounting_exact") is True)
    claim("fleet_shed_count_overload > 0",
          (fcomp.get("shed_count_overload") or 0) > 0)
    claim("fleet_merge_processes == workers_swept max",
          fcomp.get("fleet_merge_processes")
          == list(range(max((record.get("fleet") or {})
                            .get("workers_swept", [0])))))
    claim("fleet_workers_exit_zero",
          fcomp.get("workers_exit_zero") is True)
    # Disaggregation claims (wall-clock, role-split vs unified at the
    # same worker count on the long-prompt burst): decode-phase p99
    # inter-token latency at or under 0.6x the unified fleet's, exact
    # greedy parity vs the oracle on both topologies, per-role compile
    # pins unchanged, conservation (served + shed + dropped covers the
    # trace), and every request handed off exactly once on the split.
    dcomp = (record.get("disagg") or {}).get("comparison", {})
    claim("disagg_decode_p99_itl_ratio <= 0.6",
          dcomp.get("decode_p99_itl_ratio") is not None
          and dcomp["decode_p99_itl_ratio"] <= 0.6)
    claim("disagg_tokens_match_oracle",
          dcomp.get("tokens_match_oracle") is True)
    claim("disagg_zero_recompiles_per_worker",
          dcomp.get("zero_recompiles_per_worker") is True)
    claim("disagg_accounting_exact",
          dcomp.get("accounting_exact") is True)
    claim("disagg_handoffs_cover_trace",
          dcomp.get("handoffs_cover_trace") is True)
    claim("disagg_handoffs_unified_zero",
          dcomp.get("handoffs_unified_zero") is True)
    claim("disagg_workers_exit_zero",
          dcomp.get("workers_exit_zero") is True)
    # Prefix-cache claims: >= 2x prefill-token reduction and improved
    # p50 TTFT on the shared-prefix trace, ~0 hit rate honestly reported
    # on the adversarial trace, exact parity on both, and the
    # len(prompt_buckets)+len(suffix_buckets)+1 compile pin with zero
    # steady-state recompiles.
    pcomp = record.get("prefix_cache", {}).get("comparison", {})
    claim("prefix_prefill_token_reduction_shared >= 2.0",
          (pcomp.get("prefill_token_reduction_shared") or 0) >= 2.0)
    claim("prefix_p50_ttft_improved_shared",
          pcomp.get("p50_ttft_improved_shared") is True)
    claim("prefix_tokens_match_cache_off_shared",
          pcomp.get("tokens_match_cache_off_shared") is True)
    claim("prefix_tokens_match_reference_adversarial",
          pcomp.get("tokens_match_reference_adversarial") is True)
    adv_hit = pcomp.get("adversarial_hit_rate")
    claim("prefix_adversarial_hit_rate <= 0.01",
          adv_hit is not None and 0.0 <= adv_hit <= 0.01)
    shared_hit = pcomp.get("shared_hit_rate")
    claim("prefix_shared_hit_rate in (0, 1)",
          shared_hit is not None and 0.0 < shared_hit < 1.0)
    claim("prefix_zero_recompiles_with_cache",
          pcomp.get("zero_recompiles_with_cache") is True)
    # KV-hierarchy claims: >= 2x prefix hit-token recovery under the
    # constrained device pool, bitwise fp parity (incl. under the tight
    # host budget, which must actually final-evict), the int8 promote
    # logit probe inside tolerance, an exactly-0.0 int8 adversarial hit
    # rate, and the unchanged compile pin across every spill row.
    kcomp = record.get("kv_hierarchy", {}).get("comparison", {})
    claim("kv_hit_token_recovery_spill_fp >= 2.0",
          (kcomp.get("hit_token_recovery_spill_fp") or 0) >= 2.0)
    claim("kv_tokens_match_spill_off",
          kcomp.get("tokens_match_spill_off") is True)
    claim("kv_tokens_match_spill_off_tight",
          kcomp.get("tokens_match_spill_off_tight") is True)
    claim("kv_final_evictions_under_tight_budget > 0",
          (kcomp.get("final_evictions_under_tight_budget") or 0) > 0)
    claim("kv_promotes_spill_fp > 0",
          (kcomp.get("promotes_spill_fp") or 0) > 0)
    claim("kv_int8_adversarial_hit_rate == 0.0",
          kcomp.get("int8_adversarial_hit_rate") == 0.0)
    claim("kv_int8_logit_probe_ok",
          (kcomp.get("int8_logit_probe") or {}).get("ok") is True)
    claim("kv_zero_recompiles_with_spill",
          kcomp.get("zero_recompiles_with_spill") is True)
    claim("kv_async_promote_p50_no_worse",
          kcomp.get("async_promote_p50_no_worse") is True)
    claim("kv_async_promote_staged_off_dispatch_path",
          kcomp.get("async_promote_staged_off_dispatch_path") is True)
    claim("kv_tokens_match_spill_off_sync_promote",
          kcomp.get("tokens_match_spill_off_sync_promote") is True)
    # Quantized-pool claims: >= 2x budget-minted blocks at the same HBM
    # budget, greedy token parity on both traces, the cached-prefix
    # logit-drift probe inside tolerance, spill recovery composing on
    # top of int8, an exactly-0.0 adversarial hit rate, and unchanged
    # compile pins with zero steady-state recompiles.
    qcomp = record.get("kv_quant", {}).get("comparison", {})
    claim("kvq_block_capacity_ratio_int8 >= 2.0",
          (qcomp.get("block_capacity_ratio_int8") or 0) >= 2.0)
    claim("kvq_tokens_match_fp_reference",
          qcomp.get("tokens_match_fp_reference") is True)
    claim("kvq_tokens_match_fp_shared",
          qcomp.get("tokens_match_fp_shared") is True)
    claim("kvq_spill_hit_token_recovery_int8 >= 2.0",
          (qcomp.get("spill_hit_token_recovery_int8") or 0) >= 2.0)
    claim("kvq_adversarial_hit_rate == 0.0",
          qcomp.get("adversarial_hit_rate") == 0.0)
    claim("kvq_logit_drift_probe_ok",
          (qcomp.get("logit_drift_probe") or {}).get("ok") is True)
    claim("kvq_zero_recompiles_with_kv_quant",
          qcomp.get("zero_recompiles_with_kv_quant") is True)

    if failures:
        print(f"{path}: {len(failures)} claim(s) FAILED:")
        for name in failures:
            print(f"  - {name}")
        return 1
    print(f"{path}: all pinned claims hold")
    return 0


if __name__ == "__main__":
    if "--check" in sys.argv[1:]:
        sys.exit(check())
    sys.exit(main())
