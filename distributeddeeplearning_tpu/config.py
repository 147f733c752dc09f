"""Config system: frozen dataclasses + dotted CLI overrides.

One canonical config module per reference workload lives in ``configs/``
(``BASELINE.json:6-12``); each exposes ``get_config() -> Config``. Overrides
use ``--override section.field=value`` with python-literal values, e.g.
``--override train.steps=500 --override mesh.dp=4``.
"""

from __future__ import annotations

import ast
import dataclasses
import importlib.util
import json
import sys
from typing import Any

from .mesh import MeshConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "resnet18"
    kwargs: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class DataConfig:
    kind: str = "synthetic_image"
    batch_size: int = 64
    image_size: int = 32
    channels: int = 3
    num_classes: int = 10
    seq_len: int = 128
    vocab_size: int = 1024
    mask_prob: float = 0.15  # MLM kinds: fraction of positions masked
    mask_token_id: int = 3  # MLM kinds: the [MASK] id
    # synthetic_mlm: >0 emits variable-length padded rows + attention_mask
    # (the padded-batch BERT workload; see data.SyntheticMLM.pad_min_len).
    pad_min_len: int = 0
    n_distinct: int = 8
    seed: int = 0
    # Held-out eval split. Synthetic kinds: ``eval_seed`` >= 0 draws eval
    # batches from a different generator seed (-1 = eval on the training
    # distribution — the right choice for the memorization-style synthetic
    # tests, where "held-out" random noise is unlearnable by construction).
    # File-backed kinds: ``eval_path`` points at a separate validation file;
    # a seed swap alone would only RESHUFFLE the training file and silently
    # report training loss as eval, so that combination is rejected.
    eval_seed: int = -1
    eval_path: str = ""
    path: str = ""  # record_file_image / token_file_*: data file
    num_threads: int = 2  # native loader worker threads
    prefetch_depth: int = 4  # native loader ring depth
    # Device-batch prefetch depth (data.prefetch): how many placed batches
    # stay in flight ahead of the step loop so H2D overlaps compute. Raise
    # when input transfer shows up between steps in the profile; each unit
    # holds one (super-)batch in HBM.
    prefetch_size: int = 2
    # Vision training augmentation (record_file_image): deterministic
    # random pad+crop / horizontal flip (data.augment_images). The eval
    # split always runs with augmentation off.
    augment: bool = False
    aug_pad: int = 4
    label_bytes: int = 1  # record_file_image: bytes per label (2 for >256 classes)

    def dataset_kwargs(self) -> dict[str, Any]:
        """Kwargs for this kind's dataset class: the intersection of its
        dataclass fields with this config's — derived from the one registry
        in ``data.py`` so a new kind cannot silently drop overrides."""
        from .data import DATASET_KINDS

        if self.kind not in DATASET_KINDS:
            raise ValueError(f"unknown dataset kind {self.kind!r}")
        cls_fields = {f.name for f in dataclasses.fields(DATASET_KINDS[self.kind])}
        return {
            k: getattr(self, k)
            for k in cls_fields
            if k != "kind" and hasattr(self, k)
        }

    def eval_dataset_kwargs(self) -> dict[str, Any]:
        """Same as :meth:`dataset_kwargs` but on the eval split (see
        ``eval_seed`` / ``eval_path``)."""
        kwargs = self.dataset_kwargs()
        if "augment" in kwargs:
            kwargs["augment"] = False  # never augment the eval split
        if "path" in kwargs:  # file-backed kind
            if self.eval_path:
                kwargs["path"] = self.eval_path
            elif self.eval_seed >= 0:
                raise ValueError(
                    f"data.eval_seed with file-backed kind {self.kind!r} only "
                    "reshuffles the training file — set data.eval_path to a "
                    "held-out file instead"
                )
            else:
                # Without a held-out file there is no eval split to draw
                # from: every eval_* metric would be training loss in
                # disguise. Say so loudly rather than report it silently.
                print(
                    f"WARNING: file-backed kind {self.kind!r} has no "
                    "data.eval_path — eval_* metrics will be computed on "
                    "the TRAINING file (training loss, not held-out eval)",
                    file=sys.stderr,
                    flush=True,
                )
            return kwargs
        if self.eval_seed >= 0 and "seed" in kwargs:
            kwargs["seed"] = self.eval_seed
        return kwargs


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    name: str = "sgd"
    lr: float = 0.1
    momentum: float = 0.9
    b1: float = 0.9
    b2: float = 0.999
    weight_decay: float = 0.0
    warmup_steps: int = 0
    schedule: str = "constant"
    grad_clip: float = 0.0


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Mixed-precision dtype policy (``precision.py``). A config BLOCK (not
    a bare string field) so future per-axis knobs — fp8 scaling recipes,
    per-collection compute dtypes — land here without a schema break.

    ``policy``: ``fp32`` (default; the step program is bit-identical to a
    build without the subsystem), ``bf16`` (fp32 master params in
    TrainState, bf16 compute copy cast per step for fwd/bwd — activations
    and gradient collectives bf16, optimizer update fp32 on masters), or
    ``bf16_full`` (additionally stores Adam moments in bf16 with
    stochastic rounding — requires ``optim.name='adamw'``). See
    docs/MIXED_PRECISION.md.
    """

    policy: str = "fp32"


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    # Fused multi-step dispatch (Trainer.fused_train_step): K > 1 runs K
    # train steps per compiled call via an on-device lax.scan over a stacked
    # super-batch — one host dispatch per K steps. K must divide steps and
    # the log/eval/save/fault cadences (train.check_fusion_cadences); keep 1
    # for fault-injection/debug runs where the host needs per-step control.
    steps_per_call: int = 1
    seed: int = 0
    task: str = "classification"
    grad_accum: int = 1
    remat: str = "none"  # none | full | dots (M2)
    zero1: bool = False  # ZeRO-1 optimizer-state sharding (M2)
    checkpoint_dir: str = ""
    save_every: int = 0
    eval_every: int = 0  # run the eval loop every K steps (0 = off)
    eval_batches: int = 8  # batches per eval pass
    # lm/mlm with a chunked_head model: sequence positions per chunked
    # cross-entropy scan step (ops/chunked_xent.py). Ignored otherwise.
    head_chunk: int = 128
    # classification: label-smoothing ε (MLPerf ResNet-50 uses 0.1).
    label_smoothing: float = 0.0
    # Megatron-style sequence parallelism: shard the LN/residual regions'
    # seq dim over tp between blocks (parallel/tp.tp_rules(sequence_parallel
    # =True) threaded through build_all). Needs mesh.tp > 1 to have effect.
    sequence_parallel: bool = False
    # Gradient-sync compression (comms_quant.py): "fp32" = uncompressed
    # auto-sharded all-reduce; "bf16"/"int8" = explicit ring all-reduce on a
    # compressed payload (int8 adds block scales + error feedback). Lossy
    # modes are pure-DP only in v1 (the Trainer fences compositions).
    grad_comm: str = "fp32"
    grad_comm_block: int = 256  # int8 quantization block size (elements)
    # Overlapped gradient sync (comms_overlap.py; docs/OVERLAP.md): > 0
    # partitions the grad pytree into ~this-many-MiB buckets in reverse
    # layer order and fires one independent collective per bucket, so XLA
    # can interleave sync with the remaining backward compute. 0 = off
    # (single post-backward sync). Pure-DP only in v1 (Trainer fences).
    grad_bucket_mb: float = 0.0
    # Cross-replica weight-update sharding (arXiv 2004.13336): "sharded"
    # turns grad sync + update into reduce-scatter -> each member updates
    # its 1/dp flat param shard (optimizer state lives in that layout —
    # ZeRO-1's endpoint) -> all-gather fresh params. "replicated" = the
    # classic all-reduce + identical update everywhere. Fences: pure-DP,
    # grad_accum=1, and optim weight_decay/grad_clip = 0 in v1
    # (comms_overlap.check_update_sharding_config fails by name).
    update_sharding: str = "replicated"
    # Hierarchical ICI+DCN gradient sync (comms_hier.py;
    # docs/MULTISLICE.md): on a hybrid mesh (mesh.dcn_dp > 1) decompose
    # each bucket's gradient collective into intra-slice reduce-scatter ->
    # cross-slice all-reduce of the 1/ici shard (the only DCN traffic) ->
    # intra-slice all-gather. "auto" (default) picks hierarchical exactly
    # when mesh.dcn_dp > 1; "flat"/"hierarchical" force. Pure-DP only in
    # v1 (comms_hier.check_comm_hierarchy_config fails by name).
    comm_hierarchy: str = "auto"
    # Mixed-precision policy block (precision.py; docs/MIXED_PRECISION.md).
    # Select with --override train.precision.policy=bf16 — NOT via
    # model.kwargs.dtype, which would train bf16 parameters with no fp32
    # masters behind them (cli.build_all clones the model's dtype from the
    # policy and rejects a conflicting explicit model.kwargs.dtype).
    precision: PrecisionConfig = dataclasses.field(
        default_factory=PrecisionConfig
    )
    log_dir: str = ""  # TensorBoard scalars + profiler traces
    profile_steps: str = ""  # "a:b" -> jax.profiler trace window
    # Debug/fault tooling (SURVEY §5): the XLA-world equivalents of the
    # reference's CUDA sanitizer hooks. The fault matrix (docs/
    # FAULT_TOLERANCE.md): "step:K" hard-kills the process before step K;
    # "nan:K" poisons the gradients of step K on device (needs
    # health.enabled to recover); "hang:K" stalls the host loop at step K
    # (the supervisor's heartbeat monitor recovers it); "corrupt:K"
    # truncates the latest checkpoint at step K then kills (exercises the
    # restore fallback). Injections fire only on supervisor attempt 0
    # (DDL_SUPERVISOR_ATTEMPT) so restarts recover rather than re-fault.
    fault_injection: str = ""
    debug_nans: bool = False  # jax_debug_nans: fail fast on NaN outputs
    debug_checks: bool = False  # jax_enable_checks: internal invariants
    # (async-collective XLA flags are a CLI switch, --xla-perf-flags, not a
    # config field: they must hit the environment before the config module —
    # an arbitrary .py — could touch the backend.)


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """On-device health guard (``health.py``): non-finite loss/grad detection
    with skip-update semantics, an EMA loss-spike detector, and the host-side
    rollback policy. Compiled into the train step when ``enabled``."""

    enabled: bool = False
    # EMA loss tracker: ema <- beta*ema + (1-beta)*loss on healthy steps.
    ema_beta: float = 0.98
    # Spike detector: loss > spike_factor * ema (after warmup) counts as an
    # anomaly and skips the update. 0 = spike detection off (non-finite
    # detection is always on while enabled).
    spike_factor: float = 0.0
    # Healthy steps the EMA must absorb before the spike detector arms —
    # early-training loss is legitimately volatile.
    ema_warmup_steps: int = 20
    # Host-side rollback policy: once this many CONSECUTIVE anomalous steps
    # are observed (via the logged metric stream, so detection lags one
    # logging interval), abandon the in-memory state and restore the last
    # durable checkpoint. 0 = never roll back (skip-update only).
    max_consecutive_anomalies: int = 0
    # Rollbacks per process before giving up (the supervisor's restart
    # budget then takes over).
    max_rollbacks: int = 2


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Restart supervisor (``supervisor.py``) for the ``supervise`` CLI
    subcommand: classifies child exits, restarts with exponential backoff +
    jitter, detects hangs via a heartbeat file, and converts SIGTERM/SIGINT
    into a preemption-safe final save in the child."""

    # Restarts (not counting the first attempt) before giving up.
    max_restarts: int = 5
    backoff_base_s: float = 1.0
    backoff_factor: float = 2.0
    backoff_max_s: float = 60.0
    # Uniform jitter as a fraction of the backoff delay (decorrelates a
    # pod's workers re-entering the compile queue together).
    backoff_jitter: float = 0.1
    # No heartbeat-file touch for this long -> the child is hung: kill and
    # restart. 0 = hang detection off. Must exceed the worst-case gap
    # between heartbeats (first-step compile + one logging interval).
    hang_timeout_s: float = 0.0
    poll_interval_s: float = 0.5
    # After forwarding SIGTERM, how long the child gets for its final
    # synchronous save before SIGKILL.
    preempt_grace_s: float = 60.0
    heartbeat_file: str = ""  # "" -> auto (a temp path per supervisor run)
    # After a CRASH/HANG exit (not clean/preempted/injected-fault), clear
    # the persistent XLA compile cache before restarting: a child that died
    # abnormally may have truncated a cache entry mid-write, and a cached
    # executable can itself be what the child keeps dying on — recompiling
    # cold is the only restart that makes progress then. Off by default:
    # the cache is ONE directory shared by every config, tool, test and
    # serving engine of the checkout (or the machine, where
    # JAX_COMPILATION_CACHE_DIR is set), and the clear wipes all of it.
    # Turn it on together with a cache directory of the run's own.
    clear_cache_on_crash: bool = False


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Unified telemetry (``telemetry.py``; docs/OBSERVABILITY.md): span
    tracing, the goodput ledger, the device memory/compile registry and
    the crash flight recorder. Off by default — the instrumented paths
    cost one truthiness check per hook when disabled (the ``--telemetry``
    CLI flag flips ``enabled`` without a config edit)."""

    enabled: bool = False
    # Output dir for trace.json / spans.jsonl / goodput.jsonl / flight_*
    # files. "" resolves quarantine-adjacent: <train.checkpoint_dir>/
    # telemetry when a checkpoint dir is set, else a temp fallback
    # (telemetry.resolve_dir).
    dir: str = ""
    # Completed spans kept in the bounded ring (memory cap; the Chrome
    # trace exports whatever the ring holds — the most recent history).
    ring_size: int = 4096
    # Spans + events dumped per crash flight record.
    flight_last: int = 256
    trace_file: str = "trace.json"
    goodput_file: str = "goodput.jsonl"


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Serving engine (``serving/``; ``serve`` CLI subcommand): continuous
    batching over a paged KV cache with AOT prefill/decode programs. See
    docs/SERVING.md for the sizing math behind these knobs."""

    # Decode lanes: the decode program's fixed batch size. More lanes =
    # more throughput until the pool or the matmul saturates, at the cost
    # of per-token latency (docs/TUNING.md).
    slots: int = 4
    # KV tokens per pool block. Small blocks waste less pool on the last
    # partial block per sequence but grow the page table.
    block_size: int = 16
    # HBM budget for the KV pool (all layers together); the engine derives
    # num_blocks from it via a shape probe of the actual model.
    hbm_budget_mb: int = 128
    # Hard cap on prompt + generated tokens per request. 0 = model max_len.
    max_seq_len: int = 0
    # Prefill shape buckets: a prompt is right-padded to the smallest
    # bucket that fits, so there is one compiled prefill per bucket and
    # steady state never recompiles. Must be strictly increasing and leave
    # room for generation under max_seq_len.
    prompt_buckets: tuple = (32, 128, 512)
    # "int8": block-quantized weights (serving/quant.py), dequant-on-use.
    quant: str = "none"
    quant_block: int = 256
    # Stop decoding a request when it emits this token (-1 = run to
    # max_new_tokens; byte-tokenizer CLI serving has no EOS).
    eos_id: int = -1
    # Emit queue-depth / free-block gauges (metrics.serving_gauges) every
    # this many engine steps through the engine's event stream. 0 = off.
    gauge_every: int = 0
    # A demand on the decode hot loop's read path, not the switch: the
    # engine chooses by serving.engine.read_path from what it observes
    # (in place on a TPU over unquantized per-head K/V pools, no window
    # layer, no speculation, block_size % 8 == 0, kv_heads * head_dim a
    # multiple of 128; else the gather).
    # 'reference' demands nothing, and keeps its name only because the
    # benchmark's files spell it (ROADMAP D3 drops the field once the
    # latent and windowed reads are in place too). 'pallas' demands the
    # in-place read (ops/paged_attention.py reads a lane's live pages
    # through the scalar-prefetched page table; interpret mode off-TPU, so
    # the parity tests run it everywhere; block_size % 8 == 0 and the
    # other fences by name at config time); 'gather' demands the copy of
    # every page of every lane (the oracle chip_smoke.py sets the kernel
    # against).
    attn_kernel: str = "reference"
    # Prefill/decode priority: cap request admissions (one prefill each)
    # per engine step so queue bursts interleave between decode steps
    # instead of stalling the running batch. 0 = uncapped (admit while
    # lanes + blocks last).
    max_prefills_per_step: int = 0
    # Speculative decoding on the decode hot loop: "off" or "ngram:K".
    # "ngram:K" self-drafts up to K tokens per lane per step by n-gram
    # lookup over the request's own prompt+generated history (no draft
    # model), verifies all K+1 positions in ONE batched forward over the
    # paged cache, and accepts the longest greedy-matching prefix —
    # token-for-token identical to non-speculative greedy. Greedy-only
    # (sampled requests are fenced at submit); requires K >= 1,
    # K < block_size, and no demanded attn_kernel='pallas' (the Pallas
    # kernel is single-token for now) — all fenced by name at config time.
    speculation: str = "off"
    # Shared-prefix KV reuse (docs/SERVING.md prefix-cache section): full
    # KV blocks become immutable and content-addressed in a hash-chained
    # prefix trie over the block pool. Admission matches the prompt
    # against the trie, maps cached blocks at refcount+1, and prefills
    # ONLY the uncached suffix (same compiled bulk-prefill body, started
    # at the cached offset via the injected seq_lens cursor); refcount-0
    # blocks are evicted LRU-leaf-first under allocation pressure. Greedy
    # output is token-for-token identical to a cache-cold engine.
    prefix_cache: bool = False
    # Extra prefill widths for the suffix-only path (prefix_cache only):
    # a short suffix is padded to the smallest fitting width drawn from
    # suffix_buckets + prompt_buckets, so a 5-token suffix after a long
    # cached prefix doesn't pay a 512-wide forward. Must be strictly
    # increasing, disjoint from prompt_buckets, and below the largest
    # prompt bucket — fenced by name. Compile pin becomes
    # len(prompt_buckets) + len(suffix_buckets) + 1 (+1 with
    # speculation), still zero steady-state recompiles.
    suffix_buckets: tuple = ()
    # KV memory hierarchy (prefix_cache only; docs/SERVING.md memory-
    # hierarchy section): host-RAM budget, in BLOCKS, for evicted prefix
    # KV. 0 = no host tier (eviction destroys, PR 15 behavior). > 0:
    # eviction demotes the victim's KV to a host-side store instead —
    # the trie node survives and admission matches through it; promotion
    # re-uploads overlapped with the suffix prefill. The host ledger has
    # its own LRU; its second eviction is final. Requires
    # prefix_cache=true — fenced by name.
    spill_blocks: int = 0
    # Spill payload codec: 'fp' keeps the pool dtype bitwise (warm-vs-
    # cold greedy parity stays exact), 'int8' block-quantizes through
    # comms_quant (~4x more spilled tokens per host byte; promoted
    # logits drift within the tolerance tests/test_serving_spill.py
    # pins). Only meaningful with spill_blocks > 0 — fenced.
    spill_codec: str = "fp"
    # Quantized DEVICE-resident paged KV (docs/SERVING.md quantized-KV
    # section): 'off' stores pool blocks in the model dtype; 'int8'
    # stores them as int8 with one f32 scale per (page slot, kv head)
    # D-vector in a parallel scale pool — quantized once at scatter
    # (write) time, dequantized inline on the read path (fused into the
    # Pallas per-page DMA; dequant-on-gather in the reference kernel),
    # so the same HBM budget mints ~2-4x more pool blocks (the engine's
    # sizing probe measures the real per-block bytes). fp32 attention
    # carries are unchanged; greedy output matches the fp pool token for
    # token on tests/test_serving.py's trace. Incompatible by name
    # with spill_codec='int8' (spilled payloads are ALREADY int8 —
    # double quantization would compound error for zero bytes saved).
    kv_quant: str = "off"
    # Engine replication (serving/router.py; docs/SERVING.md router
    # section): number of identical ServingEngine replicas behind a
    # ReplicaRouter — in-process on CPU sim, one mesh/device group per
    # replica on hardware. 1 = a single engine, no router tier.
    replicas: int = 1
    # Router dispatch policy: 'least_loaded' scores every live replica
    # from its freshly-pulled scheduler gauges (queue depth, busy lanes,
    # pool occupancy) at each dispatch; 'round_robin' rotates blindly;
    # 'prefix_affinity' (requires prefix_cache) probes each replica's
    # prefix-trie digest and sends the request where the most prompt KV
    # is already cached, tie-breaking on load and falling back to
    # least-loaded when the affinity target is already a full lane-batch
    # deeper in queue than the idlest replica (no starvation).
    router_policy: str = "least_loaded"
    # SLO-aware admission shedding at the router: 'off' admits every
    # request (deadline expiry still drops QUEUED requests engine-side);
    # 'deadline' refuses a request at the front door — typed
    # 'request_shed' event, no prefill ever spent — when its estimated
    # queue-wait + prefill (replica latency-histogram percentiles,
    # floored by the live oldest_queued_age_s gauge) already overruns
    # its deadline_s.
    shed_policy: str = "off"
    # Which percentile of the replica's observed queue-wait / prefill
    # latency feeds the shed feasibility estimate. Higher = more
    # conservative admission = more shedding.
    shed_percentile: float = 50.0
    # Cross-process fleet (serving/worker.py + serving/net.py; ``cli
    # serve --fleet N``): each replica is a real child process serving
    # one engine behind a length-prefixed-JSON socket. The knobs below
    # only matter on that path — in-process replicas probe gauges
    # directly and never heartbeat.
    #
    # Seconds between a worker's pushed heartbeats (scheduler gauges +
    # prefix-trie digest summary). Must be > 0 when a fleet is launched
    # — fenced by name in check_fleet_composition.
    heartbeat_interval_s: float = 0.05
    # Quarantine a socket replica after this many seconds without a
    # heartbeat: its queued (never-admitted) requests reroute to the
    # survivors, its in-flight requests retry on them under a bumped
    # attempt epoch. 0 disables staleness quarantine; when > 0 it must
    # exceed heartbeat_interval_s — fenced by name. The sweep cannot
    # see inside a worker: a single-threaded worker cannot heartbeat
    # mid-engine-step, and a fresh process's first step can sit in XLA
    # compilation for multiple seconds, so the default must sit above
    # worst-case cold-step latency or every cold boot false-trips a
    # hang quarantine + respawn (a fresh CPU-sim worker's first step —
    # backend init + prefill compile — has been observed at ~5s).
    heartbeat_timeout_s: float = 10.0
    # Interface fleet workers bind/advertise. Workers always bind an
    # ephemeral port unless worker_port > 0 (then worker i binds
    # worker_port + i).
    worker_host: str = "127.0.0.1"
    worker_port: int = 0
    # Fleet self-healing (serving/fleet_supervisor.py; docs/
    # FAULT_TOLERANCE.md serving section). Per-worker restart budget: a
    # dead worker (crash / hang / lost socket) is respawned up to this
    # many times with exponential backoff; once exhausted the fleet
    # degrades gracefully to the survivors. 0 = never restart (PR 18
    # behavior: quarantine forever). Must be >= 0 — fenced by name.
    max_worker_restarts: int = 3
    # Exponential-backoff schedule between respawns of the SAME worker:
    # sleep min(base * 2**k, max) * (1 + 0.1*jitter) before attempt k.
    # Mirrors the training supervisor's schedule (supervisor.py).
    restart_backoff_base_s: float = 0.5
    restart_backoff_max_s: float = 15.0
    # Seconds between a worker's periodic KV spill-store checkpoints
    # (engine.save_spill_store) — the persistence a RESTARTED worker
    # re-warms its host tier from (crashes can't run the drain-time
    # save). 0 = only checkpoint on clean drain/SIGTERM. Requires
    # spill_blocks > 0 to matter; fenced by name when set without it.
    spill_checkpoint_every_s: float = 0.0
    # At-most-once retry of IN-FLIGHT requests when their worker dies:
    # true re-submits them on a live survivor under a bumped attempt
    # epoch (late/duplicate result frames from the half-dead worker are
    # discarded by epoch — never double-delivered); false keeps the
    # PR 18 behavior (in-flight requests die as request_failed). Queued
    # never-admitted requests reroute token-identically either way.
    request_retry: bool = True
    # Fault-injection DSL for the serving chaos harness
    # (tools/serve_chaos.py): "" = off, else one of
    # 'worker_crash:K' (os._exit(EXIT_FAULT) at engine step K),
    # 'worker_hang:K' (stop reading/heartbeating/stepping at step K;
    # process stays alive), 'conn_drop:K' (close the router socket at
    # step K), 'heartbeat_stall:K' (suppress heartbeats from step K on
    # while SERVING CONTINUES — the half-dead duplicate-result case).
    # One-shot and armed per-process like the training faults: only the
    # worker whose replica index matches $DDL_SERVE_FAULT_WORKER
    # (default 0) on its FIRST attempt fires; restarts are disarmed via
    # the attempt env. Fleet-only — fenced by name under in-process
    # `serve` (check_serving_composition).
    fault_injection: str = ""
    # Disaggregated prefill/decode serving (docs/SERVING.md
    # disaggregation section). Per-engine phase role:
    #   'unified' — the PR 18 behavior: every replica prefills AND
    #     decodes (default, fully back-compatible);
    #   'prefill' — the engine runs bulk/suffix prefill only, publishes
    #     the prompt's KV blocks into its prefix trie, and queues a
    #     handoff (chain digests + raw block bytes) instead of decoding;
    #   'decode'  — the engine adopts handed-off chains into its own
    #     trie/pool and serves the decode phase.
    # role != 'unified' requires prefix_cache=true (the trie IS the
    # handoff ledger); 'prefill' is incompatible with speculation
    # (drafting is decode-side work); any split role under
    # static batching is NotImplementedError. All fenced by name.
    role: str = "unified"
    # Fleet topology split for `cli serve --fleet N`: the first
    # prefill_replicas workers boot with role='prefill', the rest with
    # role='decode'. 0 = no split (every worker keeps serving.role,
    # normally 'unified'). Must satisfy 0 < prefill_replicas < fleet
    # when set — a fleet needs at least one of each phase — and
    # requires prefix_cache=true. Fenced in check_fleet_composition.
    prefill_replicas: int = 0
    # Upper bound, in WHOLE BLOCKS, on one binary KV handoff frame's
    # body; a longer chain is shipped as several frames (same request,
    # ascending `part` index) so no frame outgrows the wire cap. Must
    # be >= 1 — fenced by name.
    handoff_blocks_per_frame: int = 64


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    health: HealthConfig = dataclasses.field(default_factory=HealthConfig)
    supervisor: SupervisorConfig = dataclasses.field(
        default_factory=SupervisorConfig
    )
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, default=str)


def load_config(path: str) -> Config:
    """Import a config module by file path and call its ``get_config()``."""
    spec = importlib.util.spec_from_file_location("_ddl_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = mod.get_config()
    if not isinstance(cfg, Config):
        raise TypeError(f"{path}: get_config() returned {type(cfg)}, not Config")
    return cfg


def apply_overrides(cfg: Config, overrides: list[str]) -> Config:
    """Apply ``section.field=value`` overrides (values are python literals;
    bare words fall back to strings)."""
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override {item!r} is not of the form a.b=value")
        dotted, raw = item.split("=", 1)
        parts = dotted.split(".")
        try:
            value = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            value = raw
        cfg = _replace_nested(cfg, parts, value, dotted)
    return cfg


def _coerce(value, current, dotted: str):
    """Coerce a string override to the type of the current field value, so
    e.g. ``zero1=false`` can't silently become a truthy string."""
    if not isinstance(value, str) or isinstance(current, str):
        return value
    if isinstance(current, bool):
        lowered = value.lower()
        if lowered in ("true", "1", "yes"):
            return True
        if lowered in ("false", "0", "no"):
            return False
        raise ValueError(f"{dotted}: {value!r} is not a boolean")
    if isinstance(current, (int, float)):
        raise ValueError(
            f"{dotted}: {value!r} is not a valid {type(current).__name__}"
        )
    if dataclasses.is_dataclass(current):
        # e.g. ``train.precision=bf16`` would silently replace the nested
        # PrecisionConfig with a bare string; demand the field path.
        names = ", ".join(f.name for f in dataclasses.fields(current))
        raise ValueError(
            f"{dotted} is a config block, not a field — set "
            f"{dotted}.<field>=... (fields: {names})"
        )
    return value


def _replace_nested(obj, parts: list[str], value, dotted: str = ""):
    field = parts[0]
    if isinstance(obj, dict):
        # Dict-valued config fields (model.kwargs): overrides may both
        # replace existing keys (type-coerced) and introduce new ones —
        # model kwargs legitimately vary per model.
        if len(parts) == 1:
            if field in obj:
                value = _coerce(value, obj[field], dotted or field)
            return {**obj, field: value}
        if field not in obj:
            raise KeyError(f"no key {field!r} in config dict ({dotted})")
        return {
            **obj,
            field: _replace_nested(obj[field], parts[1:], value, dotted),
        }
    if not dataclasses.is_dataclass(obj) or field not in {
        f.name for f in dataclasses.fields(obj)
    }:
        raise KeyError(f"no config field {field!r} on {type(obj).__name__}")
    if len(parts) == 1:
        value = _coerce(value, getattr(obj, field), dotted or field)
        return dataclasses.replace(obj, **{field: value})
    inner = _replace_nested(getattr(obj, field), parts[1:], value, dotted)
    return dataclasses.replace(obj, **{field: inner})
