"""Continuous-batching serving engine over the paged KV cache.

The TPU-native split (docs/SERVING.md): generation is TWO compiled
programs, not one fused loop like :func:`generate.generate`:

- **prefill** — one B=1 forward over the whole (bucket-padded) prompt:
  writes the prompt's KV into the request's pool pages and samples the
  first token from the real last position (``generate.logits_at``).
  Compiled once per PROMPT BUCKET — prompts are right-padded up to the
  smallest configured bucket that fits, so any prompt length hits an
  existing executable.
- **decode** — one token for the WHOLE in-flight batch per call, B =
  ``serving.slots`` always (idle lanes ride along pointed at the null
  block). One shape forever → compiled exactly once.

Both are AOT-compiled (``jax.jit(...).lower(...).compile()``), so
steady-state serving executes cached executables only — the engine counts
compilations (``num_compiles``) and tests pin the count: admitting,
finishing, and re-admitting requests of any mix of lengths never triggers
a recompile.

The KV pool arrays are batch-independent (``transformer.
paged_decode_attention``), so the SAME pool serves both programs: the
prefill cache argument is the decode cache with its ``page_table`` /
``seq_lens`` leaves swapped for B=1 host arrays, and the updated pool
leaves are folded back afterwards. The HOST is the source of truth for
page tables and sequence lengths — they are rebuilt from scheduler state
and injected by leaf name into the cache pytree before every call, so the
device-side cursor copies are write-only.

A model with WINDOW layers (``models/cohere2_moe.py``) keeps two kinds of
layer state side by side (docs/SERVING.md): the global layers' pool above
on ``page_table`` / ``scheduler.pool``, and the window layers'
``pool_key_window`` / ``pool_value_window`` on ``page_table_window`` /
``scheduler.window_pool``, of which a lane reserves a ring of
``window / block_size + 1`` blocks however long it grows. Both tables hold
a lane's logical blocks; the host maps only the ring that ends at the
cursor's block (``_map_window``, span ``window_pages``) and points the rest
at the null block. ``hbm_budget_mb`` buys both kinds.

Sampling is per-REQUEST inside the compiled graphs: temperature / top_k /
top_p ride as [B] operands (0 = off / greedy), and each lane carries its
own PRNG key chain (``fold_in(seed, request_id)``), so one decode batch
can mix greedy and sampled requests and a request's tokens do not depend
on its batchmates. Every program ends in ONE conditional over three
samplers, chosen on the device from those operands by :func:`sampler_arm`
(the rule ``generate._make_pick`` applies with static flags): ``greedy``
(no lane samples: the argmax alone, ``rng`` untouched), ``plain`` (some
lane samples, none of them filters: temper, split, draw) and ``filtered``
(the per-row ``generate._filter_logits`` and its whole-vocabulary sort
before the draw). The host counts the arm of every call from its own
copy of the operands (``stats()["sampler"]``).

With ``serving.speculation='ngram:K'`` a THIRD program joins the pair: a
**verify** executable that scores K+1 positions per lane in one batched
forward ([S, K+1] tokens — the pending token plus up to K host-drafted
continuations from ``scheduler.ngram_draft``). The host accepts the
longest prefix of drafts matching the per-position greedy argmax (always
>= 1 token: position 0's argmax IS the plain decode output, so a
fully-rejected draft degenerates to a normal step), then REWINDS by
simply not advancing the cursor past the accepted run — the device-side
KV written for rejected positions is dead by construction, because the
next step's K+1-token scatter re-covers those positions before any
attention read, and the host-authoritative ``_lens`` is re-injected
every call. No block is allocated or freed for drafting: reservations
already cover the worst case, and draft writes past a row's reservation
land in the null block (the page table is sized one draft-window wider
than ``max_seq_len`` so they can never clamp into a live block).
Greedy-only (sampled requests are fenced at submit), so speculative
output is token-for-token identical to the non-speculative engine.

With ``serving.prefix_cache=True`` the pool runs the content-addressed
prefix trie (``scheduler.KVBlockPool``) and admission becomes
**suffix-only prefill**: trie-matched blocks are mapped into the page
table at refcount+1 and the SAME bulk-prefill body runs over just the
uncached suffix — no new compiled program, because positions, the causal
mask, and RoPE all derive from the injected ``seq_lens`` leaf, so
injecting ``seq_lens = cached_len`` instead of 0 starts the prefill at
the offset (writes land past the cached blocks; the suffix attends to
cached KV through the shared page table). ``serving.suffix_buckets``
adds short prefill widths so a 5-token suffix doesn't pay a 512-wide
executable; the compile pin moves to ``len(prompt_buckets) +
len(suffix_buckets) + 1`` (+1 with speculation), still with zero
steady-state recompiles. A FULL-prefix hit (everything but the last
prompt token cached) skips prefill entirely: the lane is armed with the
last prompt token as pending input and the first token comes from the
next batched decode/verify step. Prompt blocks are published into the
trie right after prefill (their KV is final then); generation-extended
full blocks are published at completion — EXCEPT the block holding the
final sampled token, whose KV was never written (the token is sampled
but never fed back), so a block-aligned finish withholds its last block
rather than serve garbage KV to a continuation prompt.

With ``serving.spill_blocks > 0`` the trie grows a HOST tier: eviction
demotes a refcount-0 block's KV into ``_spill_store`` (host RAM, keyed
by chain hash) instead of destroying it, coalesced into ONE
``device_get`` per eviction batch. Admission matches straight through
spilled nodes; the pool re-keys them onto fresh device blocks
(``promote``) and ``_apply_promotions`` uploads the payload with
``jax.device_put`` dispatched BEFORE the suffix prefill, so the
host->device copy overlaps the prefill compute (the scatter lands in
blocks below the row's ``seq_lens`` cursor, so published-immutability
holds — same bytes, same positions). ``serving.spill_codec='int8'``
spills through ``comms_quant.block_quantize`` (~4x more spilled tokens
per byte; scales beside the payload); ``'fp'`` is bitwise-lossless so
warm-vs-cold greedy parity stays exact. Everything here is EAGER jnp —
no new compiled bodies, the compile pin above is unchanged.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..comms_quant import block_dequantize, block_quantize
from ..generate import (
    _filter_logits, logits_at, prefill, decode_step, verify_step,
)
from ..metrics import serving_event, serving_gauges
from ..models.transformer import window_ring_blocks
from ..telemetry import NULL_TELEMETRY, SPEC_ACCEPT_HIST
from .quant import dequantize_params, quantization_error, quantize_params
from .scheduler import (
    KVBlockPool, Request, RequestState, Scheduler, blocks_for,
    chain_digests, ngram_draft,
)

# Pool leaves shared between the B=1 prefill and B=slots decode programs
# (fold/spill/promote/sizing all match by NAME): the KV blocks themselves
# plus, with kv_quant='int8', the parallel per-(slot, head) scale pools
# (transformer.paged_decode_attention creates them; with kv_quant='off'
# the scale names simply never appear in the cache pytree, so every
# name-matching path degrades to the fp pair for free). A latent-attention
# model allocates ONE leaf a layer instead of the K/V pair
# (transformer.latent_paged_attention): a name here, not a second manager.
_LATENT_LEAF = "pool_latent"
# A window layer's K and V (transformer.paged_decode_attention, window=):
# a second KIND of layer state, in a pool of its own with its own page
# table, found by these names as the latent leaf is by its.
_WINDOW_LEAVES = ("pool_key_window", "pool_value_window")
_POOL_LEAVES = (
    "pool_key", "pool_value", "pool_key_scale", "pool_value_scale",
    _LATENT_LEAF, *_WINDOW_LEAVES,
)
_HOST_LEAVES = ("page_table", "page_table_window", "seq_lens")
# Per-layer counters a served program hands back with its tokens: tokens
# routed to each expert by that call (models/glm4_moe_lite.RoutedExperts).
_LOAD_LEAF = "expert_load"

# The samplers a served program ends in, cheapest first; sampler_arm
# indexes them.
SAMPLER_ARMS = ("greedy", "plain", "filtered")


def sampler_arm(temp, top_k, top_p):
    """Index into :data:`SAMPLER_ARMS` of the cheapest sampler that serves
    every lane of one call: 0 where no lane samples (``temp > 0``), 1 where
    some do and none of THOSE filters (``top_k > 0`` or ``top_p > 0``; a
    greedy lane's filters are never applied), else 2. One rule for both
    sides: the compiled programs call it on their traced [B] operands, the
    host on the NumPy arrays it passes as those operands, so the counters
    in ``stats()["sampler"]`` say what the device ran."""
    sampling = temp > 0
    filtering = sampling & ((top_k > 0) | (top_p > 0))
    return sampling.any().astype(np.int32) + filtering.any().astype(np.int32)


# serving.attn_kernel domain. 'reference' demands nothing: read_path
# decides. 'pallas' and 'gather' demand a decode read path by name (the
# parity tests and chip_smoke.py set one against the other).
ATTN_KERNELS = ("reference", "pallas", "gather")
# How a decode step (L == 1) reads a lane's K and V: every page of the
# lane's table copied out of the pool and attended whole, or the lane's
# live pages read where they lie (ops/paged_attention.py).
READ_PATHS = ("gather", "in_place")


def read_path(attn_kernel: str, *, platform: str, latent: bool,
              window: bool, width: int, kv_quant: str, speculation: str,
              block_size: int) -> str:
    """The decode read path (one of :data:`READ_PATHS`) of an engine, from
    what it can observe. A demanded path is taken as asked (the fences
    have refused by name what is not built). Undemanded
    (``attn_kernel='reference'``), the in-place read wherever it is built
    and compiled: on a TPU (elsewhere the kernel exists in interpret mode
    only, a test device), over per-head K/V pools (``latent``: the cache
    holds ``pool_latent`` leaves), in a model without window layers (no
    window in the kernel's mask), unquantized (the int8 variant has no
    reading on the chip yet: PERF.md §7), with speculation off (the verify
    forward is L > 1), whole sublane tiles a block and whole 128-lane
    rows a token (``width``, the K/V leaves' minor dimension: the chip
    cannot copy a page out of a narrower, padded pool; a toy model's). One
    rule for ``check_serving_composition`` and ``ServingEngine``, both of
    which read ``latent``, ``window`` and ``width`` off the cache's leaves
    (:func:`_cache_facts`); ``platform`` is an argument so that a CPU
    process can ask about ``'tpu'``."""
    if attn_kernel != "reference":
        return "in_place" if attn_kernel == "pallas" else "gather"
    built = (
        platform == "tpu" and not latent and not window
        and str(kv_quant or "off") == "off"
        and speculation_k(speculation or "off") == 0
        and int(block_size) % 8 == 0 and int(width) % 128 == 0
    )
    return "in_place" if built else "gather"


def _cache_facts(cache_shapes) -> dict:
    """What :func:`read_path` asks of a model's cache, read off the leaves
    of a shape-only init: whether it holds the latent leaf, whether it
    holds window leaves, and the K/V leaves' minor dimension (0: none)."""
    shapes = {
        path[-1].key: leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            cache_shapes
        )[0]
    }
    return dict(
        latent=_LATENT_LEAF in shapes,
        window=any(k in shapes for k in _WINDOW_LEAVES),
        width=shapes.get("pool_key", (0,))[-1],
    )


# serving.kv_quant domain: device pool storage codecs.
KV_QUANT_MODES = ("off", "int8")

# int8 spill codec quantization granularity (elements per scale), matching
# comms_quant's gradient path: per-256-block absmax keeps the dequant
# error bounded by ~1/127 of the block's dynamic range.
_SPILL_QBLOCK = 256

# Models validated for paged-cache serving. Everything else is fenced at
# config time (check_serving_composition) rather than failing deep inside
# a trace: capacity-MoE decode routes through expert capacity (one-token
# streams and batched prefills disagree — generate.uses_bulk_prefill),
# and pipelined models own their own step program.
SERVABLE_MODELS = ("gpt2", "llama", "glm4_moe_lite", "cohere2_moe")
# Families whose cache is the latent leaf: what the K/V pool has and the
# latent pool does not is refused by name (_check_latent_cache).
LATENT_CACHE_MODELS = ("glm4_moe_lite",)

# Router-tier knob domains (serving/router.py dispatches on these; they
# live here so the config-time fence and the ReplicaRouter constructor
# validate against one source without a circular import).
ROUTER_POLICIES = ("round_robin", "least_loaded", "prefix_affinity")
SHED_POLICIES = ("off", "deadline")


def speculation_k(spec: str) -> int:
    """Parse + validate ``serving.speculation``: ``'off'`` -> 0,
    ``'ngram:K'`` -> K (>= 1). Shared by the config-time fence and the
    engine constructor so a directly-built engine fails with the same
    message as ``check_serving_composition``."""
    spec = str(spec)
    if spec == "off":
        return 0
    head, _, tail = spec.partition(":")
    if head != "ngram" or not tail:
        raise ValueError(
            f"serving.speculation must be 'off' or 'ngram:K', got {spec!r}"
        )
    try:
        k = int(tail)
    except ValueError:
        raise ValueError(
            f"serving.speculation must be 'off' or 'ngram:K' with integer "
            f"K, got {spec!r}"
        ) from None
    if k < 1:
        raise ValueError(
            f"serving.speculation='ngram:{k}': K must be >= 1 (K=0 is "
            "spelled speculation='off')"
        )
    return k


def _check_speculation(spec: str, block_size: int, attn_kernel: str) -> int:
    """The speculation composition fences (by name, config time), shared
    verbatim by ``check_serving_composition`` and ``ServingEngine``."""
    k = speculation_k(spec)
    if k == 0:
        return 0
    if k >= block_size:
        raise NotImplementedError(
            f"serving.speculation='ngram:{k}' x block_size={block_size}: "
            "one verify step writes K positions past the row cursor and "
            "the page table is widened by exactly one draft window, so K "
            "must stay below block_size — lower K or raise block_size"
        )
    if attn_kernel == "pallas":
        raise NotImplementedError(
            f"serving.speculation='ngram:{k}' x attn_kernel='pallas': the "
            "Pallas paged-attention kernel is single-token (L == 1) and "
            "the batched verify forward needs L = K+1 — until the "
            "multi-token kernel lands, speculation runs on "
            "attn_kernel='reference'"
        )
    return k


def _check_prefix_cache(prefix_cache, suffix_buckets,
                        prompt_buckets) -> tuple[int, ...]:
    """The prefix-cache composition fences (by name, config time), shared
    by ``check_serving_composition`` and ``ServingEngine``. Returns the
    validated suffix-bucket tuple."""
    sb = tuple(int(b) for b in (suffix_buckets or ()))
    if sb and not prefix_cache:
        raise ValueError(
            f"serving.suffix_buckets={sb} x prefix_cache=False: suffix "
            "buckets only shape the suffix-only prefill path — set "
            "serving.prefix_cache=true or drop them (a silently ignored "
            "knob is a config bug)"
        )
    if not sb:
        return sb
    if list(sb) != sorted(set(sb)) or sb[0] < 1:
        raise ValueError(
            "serving.suffix_buckets must be strictly increasing positive "
            f"lengths, got {suffix_buckets!r}"
        )
    buckets = tuple(int(b) for b in prompt_buckets)
    overlap = sorted(set(sb) & set(buckets))
    if overlap:
        raise ValueError(
            f"serving.suffix_buckets {overlap} duplicate prompt_buckets "
            "entries: that width is already compiled, and the compile pin "
            "is len(prompt_buckets) + len(suffix_buckets) + 1 — pick "
            "distinct widths or drop the duplicates"
        )
    if sb[-1] >= buckets[-1]:
        raise ValueError(
            f"serving.suffix_buckets entry {sb[-1]} is not below the "
            f"largest prompt bucket {buckets[-1]}: a suffix is always "
            "shorter than its prompt, so that executable could never be "
            "selected and would be compiled for nothing"
        )
    return sb


def _check_spill(spill_blocks, spill_codec, prefix_cache) -> int:
    """The host-spill-tier composition fences (by name, config time),
    shared by ``check_serving_composition`` and ``ServingEngine``.
    Returns the validated spill budget (blocks)."""
    sb = int(spill_blocks or 0)
    if sb < 0:
        raise ValueError(
            f"serving.spill_blocks must be >= 0 (0 = no host tier), got "
            f"{spill_blocks}"
        )
    codec = str(spill_codec or "fp")
    if codec not in ("fp", "int8"):
        raise ValueError(
            f"serving.spill_codec must be 'fp' or 'int8', got "
            f"{spill_codec!r}"
        )
    if sb and not prefix_cache:
        raise ValueError(
            f"serving.spill_blocks={sb} x prefix_cache=False: the host "
            "tier stores evicted prefix-TRIE blocks, and without the trie "
            "there is nothing to spill — set serving.prefix_cache=true or "
            "spill_blocks=0"
        )
    if codec != "fp" and not sb:
        raise ValueError(
            "serving.spill_codec='int8' x spill_blocks=0: the codec only "
            "shapes the host spill tier, which spill_blocks=0 disables — "
            "a silently ignored knob is a config bug; set spill_blocks > 0 "
            "or drop the codec"
        )
    return sb


def _check_kv_quant(kv_quant, spill_codec) -> str:
    """The quantized-device-KV composition fences (by name, config time),
    shared by ``check_serving_composition`` and ``ServingEngine``.
    Returns the validated mode."""
    mode = str(kv_quant or "off")
    if mode not in KV_QUANT_MODES:
        raise ValueError(
            f"serving.kv_quant must be one of {KV_QUANT_MODES}, got "
            f"{kv_quant!r}"
        )
    if mode == "int8" and str(spill_codec or "fp") == "int8":
        raise ValueError(
            "serving.kv_quant='int8' x spill_codec='int8': the device "
            "pool is ALREADY int8, so spilled payloads are int8+scales "
            "bitwise — re-quantizing them through the spill codec would "
            "compound quantization error for zero bytes saved (redundant "
            "double quantization). Keep spill_codec='fp' (bitwise "
            "pass-through of the int8 payload) or kv_quant='off'."
        )
    return mode


def _check_latent_cache(name, kv_quant, attn_kernel, spill_codec) -> None:
    """What is not built for the latent pool leaf (by name, config time):
    the int8 pool codec and the Pallas read kernel are written for per-head
    K and V, and the int8 spill codec's accuracy on a leaf that holds a
    normed latent beside an un-normed RoPE key is not established. The
    prefix cache, fp spill, handoff and speculation match the pool by leaf
    name and run unchanged (tests/test_glm4_moe_lite.py)."""
    if name not in LATENT_CACHE_MODELS:
        return
    if str(kv_quant or "off") != "off":
        raise NotImplementedError(
            f"serving.kv_quant={kv_quant!r} x latent paged cache "
            f"({name!r}): the int8 pool quantizes one scale per (token, "
            "head) K/V vector and the latent leaf has no heads — an int8 "
            "latent pool is not built; keep kv_quant='off'"
        )
    if str(attn_kernel or "reference") == "pallas":
        raise NotImplementedError(
            f"serving.attn_kernel={attn_kernel!r} x latent paged cache "
            f"({name!r}): ops/paged_attention.py reads per-head K and V "
            "pools — an in-place latent read kernel is not built; keep "
            "attn_kernel='reference'"
        )
    if str(spill_codec or "fp") != "fp":
        raise NotImplementedError(
            f"serving.spill_codec={spill_codec!r} x latent paged cache "
            f"({name!r}): the int8 spill codec is not validated on the "
            "latent leaf; keep spill_codec='fp' (bitwise)"
        )


def _has_window_layers(cfg) -> bool:
    """Whether the model that ``cfg`` builds has window layers, asked of
    the model itself (its ``layer_types``, preset and overrides applied; a
    module is built without parameters, so this costs nothing): no list of
    families to keep. The engine asks the cache's leaves the same."""
    from .. import models

    model = models.get_model(cfg.model.name, **cfg.model.kwargs)
    return "sliding_attention" in getattr(model, "layer_types", ())


def _probe_cache(cfg):
    """The cache leaves (shapes only) of the model that ``cfg`` builds in
    paged-decode mode: what the engine's own sizing probe sees, traced
    abstractly, with no parameters and no backend."""
    from .. import models

    s = cfg.serving
    model = models.get_model(cfg.model.name, **cfg.model.kwargs).clone(
        decode=True, kv_pages=(1, int(s.block_size), 1),
        kv_quant=str(getattr(s, "kv_quant", "off") or "off"),
    )
    return jax.eval_shape(
        model.init, jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )["cache"]


def _check_window_cache(name, s) -> None:
    """What is not built for a model with window layers (by name: at
    config time for a model whose ``layer_types`` hold a window layer, and
    again by the engine for any model whose cache holds window leaves;
    ``s`` is the ServingConfig). A window layer keeps its window and
    no more, so everything that reads a lane's OLD blocks, or starts an
    L > 1 call at a cursor, would be silently wrong there:

    - the prefix cache (and with it suffix-only prefill, the host spill
      tier and the prefill/decode handoff, which all ride the trie): a
      trie node stands for every layer's K and V of its block, and the
      window layers have dropped theirs — a trie that knows what the
      window layers kept is not built (ROADMAP R2);
    - speculation: the verify forward is L > 1 at each lane's cursor, and a
      window layer's L > 1 read attends the call's own tokens only;
    - ``kv_quant='int8'`` and ``attn_kernel='pallas'``: the int8 pool has
      no ring and ``ops/paged_attention.py`` no window in its mask."""
    what = f"window layers ({name!r})"
    if getattr(s, "prefix_cache", False) or tuple(
        getattr(s, "suffix_buckets", ())
    ):
        raise NotImplementedError(
            f"serving.prefix_cache / suffix_buckets x {what}: a cached "
            "block stands for every layer's K and V and a window layer "
            "keeps its window only — suffix-only prefill would attend to "
            "keys that are gone; a trie over window layers is not built "
            "(keep prefix_cache=false)"
        )
    if int(getattr(s, "spill_blocks", 0)):
        raise NotImplementedError(
            f"serving.spill_blocks={s.spill_blocks} x {what}: the host "
            "tier stores evicted trie nodes, and the prefix trie is not "
            "built over window layers (keep spill_blocks=0)"
        )
    if str(getattr(s, "role", "unified") or "unified") != "unified" or int(
        getattr(s, "prefill_replicas", 0)
    ):
        raise NotImplementedError(
            f"serving.role={getattr(s, 'role', 'unified')!r} / "
            f"prefill_replicas x {what}: the handoff ships a prompt's "
            "blocks through the prefix trie, which is not built over "
            "window layers (keep role='unified')"
        )
    if str(getattr(s, "kv_quant", "off") or "off") != "off":
        raise NotImplementedError(
            f"serving.kv_quant={s.kv_quant!r} x {what}: the int8 pool is "
            "not built for the window layers' ring (keep kv_quant='off')"
        )
    if str(getattr(s, "speculation", "off") or "off") != "off":
        raise NotImplementedError(
            f"serving.speculation={s.speculation!r} x {what}: the verify "
            "forward scores K+1 positions at each lane's cursor, and a "
            "window layer's multi-token read sees the call's own tokens "
            "only (keep speculation='off')"
        )
    if str(getattr(s, "attn_kernel", "reference")) == "pallas":
        raise NotImplementedError(
            f"serving.attn_kernel={s.attn_kernel!r} x {what}: "
            "ops/paged_attention.py has no window in its mask (keep "
            "attn_kernel='reference')"
        )


# serving.role domain: disaggregated prefill/decode phase roles
# (docs/SERVING.md disaggregation section).
SERVING_ROLES = ("unified", "prefill", "decode")


def _check_role(role, prefix_cache, speculation) -> str:
    """The disaggregation-role composition fences (by name, config time),
    shared by ``check_serving_composition`` and ``ServingEngine``.
    Returns the validated role."""
    r = str(role or "unified")
    if r not in SERVING_ROLES:
        raise ValueError(
            f"serving.role must be one of {SERVING_ROLES}, got {role!r}"
        )
    if r != "unified" and not prefix_cache:
        raise ValueError(
            f"serving.role={r!r} x prefix_cache=False: the prefix trie IS "
            "the handoff ledger — a prefill replica publishes the prompt's "
            "blocks into its trie and a decode replica adopts them into "
            "its own, so role-split serving requires "
            "serving.prefix_cache=true"
        )
    if r == "prefill" and str(speculation or "off") != "off":
        raise ValueError(
            f"serving.role='prefill' x speculation={speculation!r}: "
            "drafting and verify are DECODE-side work and a prefill "
            "replica never decodes — set speculation='off' on prefill "
            "replicas (decode replicas may keep it)"
        )
    return r


# Fault classes the serving chaos DSL understands (config.py
# serving.fault_injection; armed in serving/worker.py, driven by
# tools/serve_chaos.py). Each spec is '<kind>:<step K>'.
SERVE_FAULT_KINDS = (
    "worker_crash", "worker_hang", "conn_drop", "heartbeat_stall"
)


def parse_fault_injection(spec) -> "tuple[str, int] | None":
    """Parse ``serving.fault_injection`` ('' or '<kind>:K') into
    ``(kind, step)``. Raises by name on unknown kinds or a bad step so a
    typo'd chaos spec dies at config time, not silently un-armed."""
    text = str(spec or "").strip()
    if not text:
        return None
    kind, sep, raw_step = text.partition(":")
    if kind not in SERVE_FAULT_KINDS:
        raise ValueError(
            f"serving.fault_injection kind must be one of "
            f"{SERVE_FAULT_KINDS}, got {spec!r}"
        )
    try:
        step = int(raw_step)
    except ValueError:
        step = -1
    if not sep or step < 0:
        raise ValueError(
            f"serving.fault_injection={spec!r}: expected '<kind>:K' with "
            "integer step K >= 0 (the engine step at which the armed "
            "worker fires the fault)"
        )
    return kind, step


def _check_fleet_healing(s, fleet: int) -> None:
    """Self-healing knob fences (config time, by name): restart budget,
    backoff shape, spill-checkpoint cadence, and the fault-injection DSL
    (fleet-only — an in-process engine has no process to kill)."""
    restarts = getattr(s, "max_worker_restarts", 0)
    if restarts < 0:
        raise ValueError(
            f"serving.max_worker_restarts must be >= 0 (0 = never "
            f"restart, quarantine forever), got {restarts}"
        )
    base = getattr(s, "restart_backoff_base_s", 0.5)
    cap = getattr(s, "restart_backoff_max_s", 15.0)
    if base <= 0 or cap < base:
        raise ValueError(
            "serving restart backoff must satisfy 0 < "
            f"restart_backoff_base_s <= restart_backoff_max_s, got "
            f"base={base} max={cap}"
        )
    cadence = getattr(s, "spill_checkpoint_every_s", 0.0)
    if cadence < 0:
        raise ValueError(
            "serving.spill_checkpoint_every_s must be >= 0 (0 = "
            f"checkpoint only on clean drain), got {cadence}"
        )
    if cadence > 0 and not getattr(s, "spill_blocks", 0):
        raise ValueError(
            "serving.spill_checkpoint_every_s x spill_blocks=0: the "
            "periodic checkpoint persists the host spill tier, which "
            "spill_blocks=0 disables — a silently ignored cadence is a "
            "config bug; set spill_blocks > 0 or drop the cadence"
        )
    fault = parse_fault_injection(getattr(s, "fault_injection", ""))
    if fault is not None and fleet < 1:
        raise NotImplementedError(
            f"serving.fault_injection={s.fault_injection!r} x in-process "
            "serve: fault injection kills/wedges a WORKER PROCESS, which "
            "only exists under `serve --fleet N` — run a fleet or drop "
            "the fault spec"
        )


def check_serving_composition(cfg, *, fleet: int = 0,
                              platform: str | None = None) -> str | None:
    """Config-time composition fences for ``serve`` (PR-5 style: fail BY
    NAME before any compile). ``cfg`` is the full Config. ``fleet`` is
    the ``--fleet N`` worker count (0 = in-process serve) — some knobs
    are only legal when real worker processes exist. Given a ``platform``
    (``jax.default_backend()``'s name; not asked here, because a fleet's
    parent must not take the chip), returns the decode read path an engine
    built from ``cfg`` on it takes (:func:`read_path`)."""
    name = cfg.model.name
    if name.endswith("_pp"):
        raise NotImplementedError(
            f"serving x pipelined model ({name!r}): the pipeline engine "
            "owns its own step program and has no decode path — serve the "
            "equivalent dense model"
        )
    if name in ("gpt2_moe", "llama_moe"):
        raise NotImplementedError(
            f"serving x capacity-MoE ({name!r}): batched paged prefill "
            "routes the whole prompt through expert capacity at once and "
            "can drop tokens a one-token stream would keep "
            "(generate.uses_bulk_prefill) — MoE serving needs the "
            "one-token prefill path, not built yet"
        )
    if name not in SERVABLE_MODELS:
        raise ValueError(
            f"serving supports decode-capable LMs {SERVABLE_MODELS}, got "
            f"model.name={name!r}"
        )
    attn = cfg.model.kwargs.get("attn_impl", "xla")
    if attn != "xla":
        raise NotImplementedError(
            f"serving x attn_impl={attn!r}: fused/ring attention kernels "
            "are a training feature — the paged decode cache runs the xla "
            "core only (set model.kwargs.attn_impl='xla' or drop it)"
        )
    s = cfg.serving
    if s.quant not in ("none", "int8"):
        raise ValueError(
            f"serving.quant must be 'none' or 'int8', got {s.quant!r}"
        )
    if s.slots < 1:
        raise ValueError(f"serving.slots must be >= 1, got {s.slots}")
    if s.block_size < 1:
        raise ValueError(
            f"serving.block_size must be >= 1, got {s.block_size}"
        )
    buckets = tuple(s.prompt_buckets)
    if not buckets or list(buckets) != sorted(set(buckets)) or buckets[0] < 1:
        raise ValueError(
            "serving.prompt_buckets must be strictly increasing positive "
            f"lengths, got {s.prompt_buckets!r}"
        )
    kernel = getattr(s, "attn_kernel", "reference")
    if kernel not in ATTN_KERNELS:
        raise ValueError(
            f"serving.attn_kernel must be one of {ATTN_KERNELS}, got "
            f"{kernel!r}"
        )
    if kernel == "pallas" and s.block_size % 8:
        raise NotImplementedError(
            f"serving.attn_kernel='pallas' x block_size={s.block_size}: "
            "the kernel streams whole pool blocks through the (8, 128) "
            "sublane tile, so block_size must be a multiple of 8 — pick a "
            "multiple of 8 or keep attn_kernel='reference'"
        )
    if getattr(s, "max_prefills_per_step", 0) < 0:
        raise ValueError(
            "serving.max_prefills_per_step must be >= 0 (0 = uncapped), "
            f"got {s.max_prefills_per_step}"
        )
    # Router tier fences (serving/router.py). replicas == 1 means "no
    # router"; the policy knobs are validated regardless so a typo'd
    # config fails before it is silently ignored.
    if getattr(s, "replicas", 1) < 1:
        raise ValueError(
            f"serving.replicas must be >= 1, got {s.replicas} — 1 serves "
            "through a single engine, > 1 fronts N replicas with a "
            "ReplicaRouter"
        )
    policy = getattr(s, "router_policy", "least_loaded")
    if policy not in ROUTER_POLICIES:
        raise ValueError(
            f"serving.router_policy must be one of {ROUTER_POLICIES}, got "
            f"{policy!r}"
        )
    # Prefix-cache fences: suffix-bucket shape, and the affinity policy's
    # dependency on the trie digest. prefix_affinity with replicas == 1 is
    # LEGAL (no router is built; a single replica trivially owns every
    # prefix), so the policy knob ports unchanged between fleet sizes.
    prefix_on = bool(getattr(s, "prefix_cache", False))
    _check_prefix_cache(
        prefix_on, getattr(s, "suffix_buckets", ()), buckets
    )
    _check_spill(
        getattr(s, "spill_blocks", 0), getattr(s, "spill_codec", "fp"),
        prefix_on,
    )
    _check_kv_quant(
        getattr(s, "kv_quant", "off"), getattr(s, "spill_codec", "fp")
    )
    _check_latent_cache(
        name, getattr(s, "kv_quant", "off"), kernel,
        getattr(s, "spill_codec", "fp"),
    )
    if _has_window_layers(cfg):
        _check_window_cache(name, s)
    if policy == "prefix_affinity" and not prefix_on:
        raise ValueError(
            "serving.router_policy='prefix_affinity' x prefix_cache=False: "
            "affinity scores replicas by their prefix-trie digest, which "
            "only exists with serving.prefix_cache=true — enable the cache "
            "or use router_policy='least_loaded'"
        )
    shed = getattr(s, "shed_policy", "off")
    if shed not in SHED_POLICIES:
        raise ValueError(
            f"serving.shed_policy must be one of {SHED_POLICIES}, got "
            f"{shed!r}"
        )
    pct = getattr(s, "shed_percentile", 50.0)
    if not 0.0 < pct <= 100.0:
        raise ValueError(
            f"serving.shed_percentile must be in (0, 100], got {pct}"
        )
    # Speculative decoding fences: format, K bounds, and the L>1 kernel
    # gap. The x-sampling fence is per-REQUEST (temperature lives on the
    # request, not the config) and fires in ServingEngine.submit.
    _check_speculation(
        getattr(s, "speculation", "off"), s.block_size, kernel
    )
    # Disaggregation fences: role domain, trie dependency, the
    # prefill x speculation conflict, and the fleet topology knobs.
    _check_role(
        getattr(s, "role", "unified"), prefix_on,
        getattr(s, "speculation", "off"),
    )
    pr = int(getattr(s, "prefill_replicas", 0))
    if pr < 0:
        raise ValueError(
            f"serving.prefill_replicas must be >= 0 (0 = no role split), "
            f"got {pr}"
        )
    if pr > 0:
        if fleet < 1:
            raise ValueError(
                f"serving.prefill_replicas={pr} x in-process serve: the "
                "role split pins WORKER PROCESSES to phases, which only "
                "exist under `serve --fleet N` — run a fleet or drop the "
                "split"
            )
        if pr >= fleet:
            raise ValueError(
                f"serving.prefill_replicas={pr} x fleet={fleet}: a split "
                "fleet needs at least one decode replica "
                "(prefill_replicas < fleet) — no one would ever emit a "
                "token"
            )
        if not prefix_on:
            raise ValueError(
                f"serving.prefill_replicas={pr} x prefix_cache=False: "
                "the prefix trie is the handoff ledger on BOTH sides of "
                "the split — set serving.prefix_cache=true"
            )
    if int(getattr(s, "handoff_blocks_per_frame", 64)) < 1:
        raise ValueError(
            "serving.handoff_blocks_per_frame must be >= 1, got "
            f"{s.handoff_blocks_per_frame}"
        )
    # Fleet self-healing fences (restart budget / backoff / checkpoint
    # cadence / fault-injection DSL).
    _check_fleet_healing(s, fleet)
    if platform is None:
        return None
    return read_path(
        kernel, platform=platform, **_cache_facts(_probe_cache(cfg)),
        kv_quant=getattr(s, "kv_quant", "off"),
        speculation=getattr(s, "speculation", "off"),
        block_size=s.block_size,
    )


class ServingEngine:
    """Continuous batching over ``cfg.slots`` decode lanes.

    ``submit()`` enqueues requests; every ``step()`` retires finished
    lanes, admits from the queue (one bucketed prefill per admission), and
    runs ONE decode call for the whole batch. ``run()`` drains to idle.

    ``model`` must be a decode-capable LM (gpt2/llama) with
    ``attn_impl='xla'``; the engine clones it into paged-decode mode
    itself. ``clock`` is injectable for deterministic tests; ``emit``
    receives ``metrics.serving_event`` records (default: collected on
    ``self.events``).
    """

    def __init__(self, model, params, cfg, *, emit=None,
                 clock=time.monotonic, seed: int = 0, telemetry=None,
                 platform: str | None = None):
        if getattr(model, "attn_impl", "xla") != "xla":
            raise NotImplementedError(
                f"serving x attn_impl={model.attn_impl!r} (see "
                "check_serving_composition)"
            )
        self.cfg = cfg
        self.clock = clock
        self.events: list[dict] = []
        self._emit = emit if emit is not None else self.events.append
        # Telemetry bundle (telemetry.py): an engine_step span a step,
        # tiled by schedule/prefill/decode and the host work around them
        # (docs/OBSERVABILITY.md), per-executable compile+memory records,
        # and an event mirror for the flight recorder. NULL when the
        # caller didn't wire one.
        self._tel = telemetry if telemetry is not None else NULL_TELEMETRY
        if self._tel.enabled:
            # One clock: every span is also an event on the host plane of
            # any jax profile taken meanwhile, beside the device ops.
            self._tel.tracer.annotate = jax.profiler.TraceAnnotation
        self.gauge_every = int(getattr(cfg, "gauge_every", 0))
        self.max_seq_len = int(cfg.max_seq_len) or int(model.max_len)
        if self.max_seq_len > int(model.max_len):
            raise ValueError(
                f"serving.max_seq_len {self.max_seq_len} exceeds the "
                f"model's max_len {model.max_len}"
            )
        self.buckets = tuple(sorted(int(b) for b in cfg.prompt_buckets))
        if self.buckets[-1] >= self.max_seq_len:
            raise ValueError(
                f"largest prompt bucket {self.buckets[-1]} leaves no room "
                f"for generation within max_seq_len {self.max_seq_len}"
            )
        # Prefix cache: shared-prefix KV reuse via the pool trie + suffix-
        # only prefill (module docstring). Suffix buckets are extra prefill
        # widths; selection falls back to the prompt buckets, so coverage
        # is guaranteed even with suffix_buckets=().
        self.prefix_cache = bool(getattr(cfg, "prefix_cache", False))
        self.suffix_buckets = _check_prefix_cache(
            self.prefix_cache, getattr(cfg, "suffix_buckets", ()),
            self.buckets,
        )
        self._prefill_widths = tuple(
            sorted(set(self.buckets) | set(self.suffix_buckets))
        )
        # Host spill tier (module docstring): budget in blocks + codec.
        self.spill_blocks = _check_spill(
            getattr(cfg, "spill_blocks", 0),
            getattr(cfg, "spill_codec", "fp"), self.prefix_cache,
        )
        self.spill_codec = str(getattr(cfg, "spill_codec", "fp") or "fp")
        # Quantized device-resident paged KV (module docstring): int8
        # blocks + parallel scale pools, quantized at scatter time,
        # dequantized on the read path. Fenced here as well as at config
        # time; the spill tier carries int8 payloads through the 'fp'
        # (bitwise) codec path — spill_codec='int8' on top is rejected
        # by name as redundant double quantization.
        self.kv_quant = _check_kv_quant(
            getattr(cfg, "kv_quant", "off"), self.spill_codec
        )
        # Disaggregation phase role (module docstring / docs/SERVING.md):
        # 'prefill' runs bulk/suffix prefill then queues a KV-chain
        # handoff instead of decoding; 'decode' adopts handed-off chains.
        # Fenced here as well as at config time — tests build engines
        # directly from a ServingConfig.
        self.role = _check_role(
            getattr(cfg, "role", "unified"), self.prefix_cache,
            getattr(cfg, "speculation", "off"),
        )
        S, bs = int(cfg.slots), int(cfg.block_size)
        self.slots_n, self.block_size = S, bs
        # Speculative decoding (module docstring): up to K host-drafted
        # tokens per lane per step, verified in one K+1-position forward.
        # Fenced here as well as at config time — tests and tools build
        # engines directly from a ServingConfig.
        self.spec_k = _check_speculation(
            getattr(cfg, "speculation", "off"), bs,
            str(getattr(cfg, "attn_kernel", "reference")),
        )
        # The page table is ONE DRAFT WINDOW wider than max_seq_len needs:
        # a verify step scatters up to spec_k positions past the cursor,
        # and the widened columns (always null-block 0) absorb those
        # writes — without the slack, jnp.take_along_axis's clamped OOB
        # gather would silently redirect an overflowing draft write into
        # the row's own LAST live block and corrupt accepted KV.
        self.pages = blocks_for(self.max_seq_len + self.spec_k, bs)

        # --- size the pool from the HBM budget --------------------------
        # Bytes per block from a shape-only init probe with num_blocks=1:
        # whatever the model actually allocates per layer, no hand model.
        # With kv_quant='int8' the probe sees the int8 pools PLUS their
        # f32 scale pools, so block_bytes shrinks ~3.8x (int8 values +
        # 4/D scale overhead) and the SAME budget mints proportionally
        # more blocks — the capacity win, measured rather than assumed.
        probe = model.clone(
            decode=True, kv_pages=(1, bs, self.pages),
            kv_quant=self.kv_quant,
        )
        tok1 = jax.ShapeDtypeStruct((S, 1), jnp.int32)
        shapes = jax.eval_shape(probe.init, jax.random.PRNGKey(0), tok1)
        leaf_bytes = [
            (path[-1].key, int(np.prod(leaf.shape)) * leaf.dtype.itemsize)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                shapes["cache"]
            )[0]
        ]
        window_bytes = sum(n for k, n in leaf_bytes if k in _WINDOW_LEAVES)
        block_bytes = sum(
            n for k, n in leaf_bytes if k in _POOL_LEAVES
        ) - window_bytes
        latent_bytes = sum(n for k, n in leaf_bytes if k == _LATENT_LEAF)
        budget = int(cfg.hbm_budget_mb) * (1 << 20)
        # One request at max_seq_len, of each kind of layer state: every
        # one of its blocks of the global kind, the ring of the window kind.
        lane_blocks = blocks_for(self.max_seq_len, bs)
        self.window_ring = self.window_blocks = 0
        if window_bytes:
            # The budget buys both kinds. A lane at max_seq_len costs
            # ``lane_blocks`` global blocks and its ring of window blocks;
            # the budget is split so that both pools hold the same number
            # of such lanes, and what the window kind cannot use (it never
            # needs more than slots x ring) goes to the global kind.
            _check_window_cache(type(model).__name__, cfg)
            self.window_ring = min(
                lane_blocks, window_ring_blocks(int(model.window), bs)
            )
            lanes = budget / (
                lane_blocks * block_bytes + self.window_ring * window_bytes
            )
            self.window_blocks = 1 + min(
                S * self.window_ring, int(lanes * self.window_ring)
            )
            budget -= self.window_blocks * window_bytes
        self.num_blocks = budget // block_bytes
        for kind, have, need, size in (
            ("KV", self.num_blocks, 1 + lane_blocks, block_bytes),
            ("window-layer KV", self.window_blocks,
             1 + self.window_ring if window_bytes else 0, window_bytes),
        ):
            if have < need:  # null + 1 request, per kind of layer state
                raise ValueError(
                    f"serving.hbm_budget_mb={cfg.hbm_budget_mb} holds "
                    f"{have} {kind} blocks of {size} B but one "
                    f"max_seq_len={self.max_seq_len} request needs "
                    f"{need} — raise the budget or lower max_seq_len"
                )
        self.block_bytes = block_bytes
        self.window_block_bytes = window_bytes
        self.kv_pages = (self.num_blocks, bs, self.pages)
        # Decode read path (docs/SERVING.md hot path): every page of a
        # lane's table gathered per layer per step, or the lane's live
        # pages read in place (ops/paged_attention.py; interpret mode
        # off-TPU, so both run and parity-test everywhere). attn_kernel is
        # what was asked, read_path what read_path() made of it and of
        # what this engine observes; ``platform`` is for a deviceless
        # compile, which builds the chip's engine in a CPU process.
        self.attn_kernel = str(getattr(cfg, "attn_kernel", "reference"))
        if self.attn_kernel not in ATTN_KERNELS:
            raise ValueError(
                f"serving.attn_kernel must be one of {ATTN_KERNELS}, got "
                f"{self.attn_kernel!r}"
            )
        self.read_path = read_path(
            self.attn_kernel, platform=platform or jax.default_backend(),
            **_cache_facts(shapes["cache"]), kv_quant=self.kv_quant,
            speculation=getattr(cfg, "speculation", "off"), block_size=bs,
        )
        self.model = model.clone(
            decode=True, kv_pages=self.kv_pages,
            paged_kernel=(
                "pallas" if self.read_path == "in_place" else "reference"
            ),
            kv_quant=self.kv_quant,
            **({"window_blocks": self.window_blocks} if window_bytes else {}),
        )
        # Prefill/decode priority: cap admissions (each costs one prefill)
        # per engine step so a queue burst cannot stall the running decode
        # batch behind back-to-back prefills. 0 = admit while lanes last.
        self.max_prefills = int(getattr(cfg, "max_prefills_per_step", 0))
        if self.max_prefills < 0:
            raise ValueError(
                "serving.max_prefills_per_step must be >= 0, got "
                f"{self.max_prefills}"
            )

        # --- params (optionally int8 weight-quantized) ------------------
        self.quant_report = None
        if cfg.quant == "int8":
            self._params, self.quant_report = quantize_params(
                params, int(cfg.quant_block)
            )
            self.quant_report["max_rel_error"] = quantization_error(
                params, int(cfg.quant_block)
            )
            self._dequant = dequantize_params
        else:
            self._params = params
            self._dequant = lambda p: p

        # --- cache: ONE concrete pytree, pool leaves authoritative ------
        shapes_S = jax.eval_shape(
            self.model.init, jax.random.PRNGKey(0), tok1
        )
        self._cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes_S["cache"]
        )

        # --- host-side scheduler + per-lane operand rows ----------------
        # Host spill store: chain hash -> ("fp"|"int8", per-pool-leaf
        # payload). The pool stays jax-free; it hands eviction victims to
        # _spill_out (one coalesced device_get per batch) and releases
        # payloads through _spill_drop.
        self._spill_store: dict[bytes, tuple] = {}
        self.spill_stats = {
            "spill_bytes": 0, "promote_bytes": 0,
            "spill_transfers": 0, "promote_transfers": 0,
        }
        self.scheduler = Scheduler(
            S,
            KVBlockPool(self.num_blocks, bs,
                        prefix_cache=self.prefix_cache,
                        spill_blocks=self.spill_blocks,
                        spill_fn=self._spill_out,
                        drop_fn=self._spill_drop),
            self.max_seq_len,
            kv_bytes_per_token=self.block_bytes // bs,
            latent_bytes_per_token=(latent_bytes // bs) or None,
            kv_quant=self.kv_quant,
            read_path=self.read_path,
            role=self.role,
            window_pool=(
                KVBlockPool(self.window_blocks, bs) if window_bytes else None
            ),
            window_ring=self.window_ring,
        )
        # Handoff queue (role='prefill'): export records awaiting pickup
        # by the worker/router — each is the request plus its chain
        # digests and captured raw block bytes. Adoption/export stats
        # feed stats() and the disagg bench block.
        self._handoffs: list[dict] = []
        self.handoff_stats = {
            "exported": 0, "export_blocks": 0, "export_bytes": 0,
            "adopted": 0, "adopt_blocks": 0, "adopt_bytes": 0,
            "adopt_skipped_blocks": 0, "adopt_fallbacks": 0,
        }
        # Promote uploads in flight, by request id: _start_promotions
        # stages them, _apply_promotions scatters them.
        self._staged_promotes: dict[int, tuple] = {}
        self._table = np.zeros((S, self.pages), np.int32)
        # The window layers' table (all null where the model has none): a
        # lane's LOGICAL blocks, of which only the ring that ends at the
        # cursor's block is mapped (_map_window); _window_at is the block
        # each lane's row was last mapped for.
        self._table_window = np.zeros((S, self.pages), np.int32)
        self._window_at = np.full((S,), -1, np.int64)
        self._lens = np.zeros((S,), np.int32)
        self._tok = np.zeros((S,), np.int32)
        self._temp = np.zeros((S,), np.float32)
        self._top_k = np.zeros((S,), np.int32)
        self._top_p = np.zeros((S,), np.float32)
        self._rng = np.zeros((S, 2), np.uint32)
        self._seed = int(seed)

        # --- compiled executables ---------------------------------------
        self._prefill_exe: dict[int, object] = {}  # bucket P -> executable
        self._decode_exe = None
        self._verify_exe = None
        self.num_compiles = 0
        self.calls = {"prefill": 0, "decode": 0, "verify": 0}
        # Sampler arm taken by each prefill and decode call (a verify call
        # has no sampler): sums to calls["prefill"] + calls["decode"].
        self.sampler = dict.fromkeys(SAMPLER_ARMS, 0)
        # Speculation yield counters (stats()):
        # drafted = draft tokens offered to verify, draft_hits = drafted
        # tokens accepted, emitted = tokens emitted by verify steps (hits
        # + one correction/bonus token per lane per step), lane_steps =
        # (lane, verify call) pairs — emitted/lane_steps is the mean
        # accepted-per-step, in [1, K+1].
        self.spec = {"drafted": 0, "draft_hits": 0, "emitted": 0,
                     "lane_steps": 0}
        self.step_count = 0
        self.draining = False

    # ------------------------------------------------------------------
    # cache plumbing: host arrays in, pool arrays shared across programs
    # ------------------------------------------------------------------

    def _inject(self, cache, table, lens, window_table=None):
        """Swap every ``page_table``/``seq_lens`` leaf (by NAME, at any
        depth — per-layer attention cursors and gpt2's position cursor
        alike) for host-built arrays of the target batch size;
        ``window_table`` (default: all null) goes to the window layers'
        ``page_table_window`` leaves."""
        tables = [(np.asarray(table), self.num_blocks)]
        if self.window_blocks:
            if window_table is None:
                window_table = np.zeros_like(tables[0][0])
            tables.append((np.asarray(window_table), self.window_blocks))
        for t, limit in tables:
            if t.size and (int(t.min()) < 0 or int(t.max()) >= limit):
                # XLA clamps OOB gather/scatter indices SILENTLY — a
                # corrupt table would read (and write) the wrong physical
                # block. The host is the source of truth for tables, so
                # range-check every injection; the traced guard in
                # paged_decode_attention covers device-built tables under
                # train.debug_checks.
                raise ValueError(
                    f"page table entry out of range [0, {limit}): "
                    f"min={int(t.min())} max={int(t.max())} — the XLA "
                    "gather would clamp this silently and corrupt another "
                    "request's KV"
                )
        table = np.asarray(table, np.int32)
        lens = np.asarray(lens, np.int32)

        def pick(path, leaf):
            name = getattr(path[-1], "key", None)
            # A FRESH device buffer per leaf: the cache argument is
            # donated, and XLA rejects donating one buffer twice — the
            # per-layer cursor leaves must not alias.
            if name == "page_table":
                return jnp.asarray(np.array(table))
            if name == "page_table_window":
                return jnp.asarray(np.array(window_table, np.int32))
            if name == "seq_lens":
                return jnp.asarray(np.array(lens))
            return leaf

        return jax.tree_util.tree_map_with_path(pick, cache)

    def _fold_pools(self, updated):
        """Adopt the pool leaves a B=1 prefill just wrote; every other
        leaf keeps its decode-batch shape."""
        self._cache = jax.tree_util.tree_map_with_path(
            lambda p, old, new: (
                new if getattr(p[-1], "key", None) in _POOL_LEAVES else old
            ),
            self._cache, updated,
        )

    def _map_window(self, state: RequestState, cur: int) -> None:
        """Point the lane's window-layer row at the ring of blocks that
        ends at logical block ``cur`` (the cursor's): block b lives in the
        ``b % n``-th of the n blocks the lane reserved, the blocks behind
        the ring and ahead of ``cur`` at the null block, so that nothing
        written there can land on a live slot
        (transformer.paged_decode_attention, the window's contract)."""
        blocks, slot = state.window_blocks, state.slot
        row = self._table_window[slot]
        row[:] = 0
        lo = max(0, cur - self.window_ring + 1)
        logical = np.arange(lo, cur + 1)
        row[logical] = np.asarray(blocks, np.int32)[logical % len(blocks)]
        # A slot handed to a newer block of the lane: a turn of the ring.
        before = max(self._window_at[slot], len(blocks) - 1)
        self.scheduler.window_ring_wraps += max(0, int(cur - before))
        self._window_at[slot] = cur

    # ------------------------------------------------------------------
    # host spill tier (KV memory hierarchy, module docstring)
    # ------------------------------------------------------------------

    def _pool_leaves(self) -> list:
        """The cache pytree's pool leaves in canonical flatten order —
        the SAME order for spill capture and promote scatter, so payload
        slot k always names the same per-layer key/value array."""
        flat = jax.tree_util.tree_flatten_with_path(self._cache)[0]
        return [
            leaf for path, leaf in flat
            if getattr(path[-1], "key", None) in _POOL_LEAVES
        ]

    def _spill_out(self, pairs: list[tuple[int, bytes]]) -> None:
        """Pool eviction callback: capture the victims' device KV into the
        host store BEFORE their blocks can be reused. ONE coalesced
        ``device_get`` per eviction batch (a tuple transfer), however many
        blocks one admission squeezed out. Safe synchronously: admission
        is host-sequential, so nothing rewrites the blocks between the
        pool's callback and the copy. fp payloads keep the pool dtype
        bitwise; int8 quantizes per 256-element block with the scale
        stored beside the payload."""
        leaves = self._pool_leaves()
        ids = np.asarray([b for b, _ in pairs], np.int32)
        host = jax.device_get(tuple(leaf[ids] for leaf in leaves))
        self.spill_stats["spill_transfers"] += 1
        for i, (_, h) in enumerate(pairs):
            rows = [np.asarray(arr[i]) for arr in host]
            if self.spill_codec == "int8":
                payload = []
                nbytes = 0
                for row in rows:
                    flat = np.asarray(row, np.float32).reshape(-1)
                    pad = (-flat.size) % _SPILL_QBLOCK
                    if pad:
                        flat = np.concatenate(
                            [flat, np.zeros(pad, np.float32)]
                        )
                    q, s = block_quantize(
                        jnp.asarray(flat), _SPILL_QBLOCK
                    )
                    q, s = np.asarray(q), np.asarray(s)
                    payload.append((q, s))
                    nbytes += q.nbytes + s.nbytes
                self._spill_store[h] = ("int8", payload)
            else:
                nbytes = sum(r.nbytes for r in rows)
                self._spill_store[h] = ("fp", rows)
            self.spill_stats["spill_bytes"] += nbytes

    def _spill_drop(self, chain_hash: bytes) -> None:
        """Pool drop callback: a host node left the trie (final eviction,
        adoption, flush) — release its payload."""
        self._spill_store.pop(chain_hash, None)

    def _start_promotions(self, state: RequestState) -> None:
        """Stage ``state``'s promoted-chain uploads: pop the spill-store
        payloads and DISPATCH the ``jax.device_put`` copies now, parking
        the in-flight device buffers in ``_staged_promotes`` for the
        scatter in :meth:`_apply_promotions`. ``device_put`` is async, so
        everything the engine does between here and the scatter — other
        admissions' prefills, the preceding decode's tail — overlaps the
        H2D copy. ``step()`` calls this for every admitted state at
        admission/match time, before the first prefill dispatches. Staged
        nodes carry refcount >= 1 (the admission acquired the chain), so
        they cannot be re-spilled before the scatter lands."""
        pairs = state.promoted
        if not pairs:
            return
        state.promoted = []
        t0 = time.perf_counter()
        payloads = []
        codec = "fp"
        for _, h in pairs:
            codec, payload = self._spill_store.pop(h)
            payloads.append(payload)
        ids = jnp.asarray(np.asarray([b for b, _ in pairs], np.int32))
        n = len(pairs)
        uploads = []
        nbytes = 0
        n_leaves = len(payloads[0])
        for j in range(n_leaves):
            if codec == "int8":
                qs = np.stack([p[j][0] for p in payloads])
                ss = np.stack([p[j][1] for p in payloads])
                up = (jax.device_put(qs), jax.device_put(ss))
                nbytes += qs.nbytes + ss.nbytes
            else:
                rows = np.stack([p[j] for p in payloads])
                up = jax.device_put(rows)
                nbytes += rows.nbytes
            uploads.append(up)
        self.spill_stats["promote_bytes"] += nbytes
        self.spill_stats["promote_transfers"] += 1
        self._staged_promotes[state.request.request_id] = (codec, ids, n,
                                                           uploads)
        self._tel.hist("promote_stage").record(time.perf_counter() - t0)

    def _apply_promotions(self, state: RequestState) -> None:
        """Scatter ``state``'s staged promoted-chain uploads into the
        pool. Scattered rows land in blocks the page table maps BELOW the
        row's ``seq_lens`` cursor with exactly the bytes the trie
        published there (bitwise for fp), so published-block immutability
        holds. ``promote_wait`` measures the host time this request's
        prefill dispatch spends on promotion: the upload is already in
        flight (:meth:`_start_promotions`), so scatter dispatch only."""
        t0 = time.perf_counter()
        staged = self._staged_promotes.pop(state.request.request_id, None)
        if staged is None:
            return
        codec, ids, n, uploads = staged
        it = iter(uploads)

        def scatter(path, leaf):
            if getattr(path[-1], "key", None) not in _POOL_LEAVES:
                return leaf
            up = next(it)
            if codec == "int8":
                q, s = up
                flat = block_dequantize(
                    q.reshape(-1, _SPILL_QBLOCK), s.reshape(-1, 1)
                )
                row_elems = int(np.prod(leaf.shape[1:]))
                rows = flat.reshape(n, -1)[:, :row_elems].reshape(
                    (n,) + leaf.shape[1:]
                )
            else:
                rows = up
            return leaf.at[ids].set(rows.astype(leaf.dtype))

        self._cache = jax.tree_util.tree_map_with_path(
            scatter, self._cache
        )
        # Dispatch wait, not completion wait: the copy+scatter run behind
        # the suffix prefill; PR 12's fleet merge aggregates this per
        # replica.
        self._tel.hist("promote_wait").record(time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # prefill/decode disaggregation (docs/SERVING.md): block export on
    # the prefill side, chain adoption on the decode side. The block is
    # the transfer unit, the trie is the handoff ledger.
    # ------------------------------------------------------------------

    def _capture_blocks(self, ids: list[int]) -> tuple[list[bytes], int]:
        """Copy pool rows ``ids`` to host as raw per-block byte strings:
        one coalesced ``device_get`` for the whole chain, then each
        block's payload is its pool-leaf rows concatenated in
        ``_pool_leaves`` order — bitwise, whatever ``kv_quant`` is, so an
        int8 pool ships ~3.2x fewer bytes with NO re-quantization on the
        wire (the scale rows ride along as two of the four leaves).
        Returns ``(payloads, total_bytes)``; each payload is exactly
        ``self.block_bytes`` long, which the receiver verifies."""
        if not ids:
            return [], 0
        leaves = self._pool_leaves()
        idx = np.asarray(ids, np.int32)
        host = jax.device_get(tuple(leaf[idx] for leaf in leaves))
        payloads = [
            b"".join(
                np.ascontiguousarray(arr[i]).tobytes() for arr in host
            )
            for i in range(len(ids))
        ]
        return payloads, sum(len(p) for p in payloads)

    def _queue_handoff(self, state: RequestState, *, written: int) -> None:
        """Prefill-side half of a handoff: export the prompt's cached
        chain (digests + pool rows), retire the lane WITHOUT finishing
        the request, and park the capture on ``_handoffs`` for the
        worker pump to frame out. ``written`` is the count of prompt
        positions whose KV this replica actually wrote — ``len(prompt)``
        after a prefill, ``len(prompt) - 1`` on the decode route (a
        full-prefix hit never ran a forward, so the LAST prompt token's
        KV does not exist yet; publishing through it would hand off a
        block with one garbage position when the prompt length lands on
        a block boundary). The export itself needs no ``written`` cap:
        ``chain_digests`` stops at ``(len(prompt) - 1) // block_size``
        full blocks, which never reaches the last prompt position on
        either path. The capture happens before ``complete_handoff``
        releases the chain refs, so no eviction can recycle the rows
        under the ``device_get``."""
        req, slot = state.request, state.slot
        digests, ids = self.scheduler.pool.export_chain(req.prompt)
        payloads, nbytes = self._capture_blocks(ids)
        self.scheduler.complete_handoff(slot, self.clock(), written=written)
        self._temp[slot] = 0.0
        self._lens[slot] = 0
        self._table[slot] = 0  # park the lane on the null block
        self._handoffs.append({
            "state": state,
            "request": req,
            "digests": digests,
            "payloads": payloads,
        })
        self.scheduler.handoff_queue_depth = len(self._handoffs)
        self.scheduler.handoff_bytes_total += nbytes
        st = self.handoff_stats
        st["exported"] += 1
        st["export_blocks"] += len(ids)
        st["export_bytes"] += nbytes
        self._event(
            "request_handoff", state, slot=slot,
            blocks=len(ids), kv_bytes=nbytes,
        )

    def take_handoffs(self) -> list[dict]:
        """Drain the pending handoff queue (worker pump / in-process
        router hook). Each record carries the retired ``state``, its
        ``request``, the chain ``digests``, and the raw block
        ``payloads`` — everything a transport needs to build KV frames."""
        out, self._handoffs = self._handoffs, []
        self.scheduler.handoff_queue_depth = 0
        return out

    def _scatter_raw_blocks(self, blocks: list[int],
                            raws: list[bytes]) -> int:
        """Write wire block payloads into freshly-alloc'd pool rows:
        the exact inverse of :meth:`_capture_blocks` — re-slice each
        payload by the pool leaves' row dtype/shape (bfloat16 rows
        reconstruct via ``ml_dtypes`` through ``np.frombuffer``), ONE
        ``device_put`` per leaf for the whole batch, one fused scatter
        over the cache. Raises ``ValueError`` on a size mismatch (sender
        pool layout differs) BEFORE any device write."""
        leaves = self._pool_leaves()
        per_leaf: list[list[np.ndarray]] = [[] for _ in leaves]
        nbytes = 0
        for raw in raws:
            off = 0
            for j, leaf in enumerate(leaves):
                shape = leaf.shape[1:]
                dt = np.dtype(leaf.dtype)
                count = int(np.prod(shape))
                nb = count * dt.itemsize
                if off + nb > len(raw):
                    raise ValueError(
                        f"handoff block payload is {len(raw)} bytes; "
                        f"this pool's blocks are {self.block_bytes} — "
                        "sender kv_quant/model layout differs"
                    )
                per_leaf[j].append(
                    np.frombuffer(
                        raw, dtype=dt, count=count, offset=off
                    ).reshape(shape)
                )
                off += nb
            if off != len(raw):
                raise ValueError(
                    f"handoff block payload is {len(raw)} bytes; "
                    f"this pool's blocks are {off} — "
                    "sender kv_quant/model layout differs"
                )
            nbytes += off
        ids = jnp.asarray(np.asarray(blocks, np.int32))
        uploads = [jax.device_put(np.stack(rows)) for rows in per_leaf]
        it = iter(uploads)

        def scatter(path, leaf):
            if getattr(path[-1], "key", None) not in _POOL_LEAVES:
                return leaf
            return leaf.at[ids].set(next(it))

        self._cache = jax.tree_util.tree_map_with_path(
            scatter, self._cache
        )
        return nbytes

    def adopt_chain(self, prompt: list[int], payloads: list[bytes], *,
                    offset: int = 0) -> int:
        """Decode-side half of a handoff: graft ``payloads`` — raw block
        bytes for chain positions ``offset .. offset+len(payloads)`` of
        ``prompt`` — into this replica's pool/trie, so the request's
        subsequent :meth:`submit` admits as a (near-)full prefix hit.
        Dedupes against local state first (``match_digests``): positions
        the trie already holds are skipped, so a shared prefix transfers
        once however many requests ride it. Degrades, never breaks:
        a stale slice (the sender skipped blocks this pool no longer
        holds) or an unallocatable pool adopts NOTHING and returns 0 —
        the request simply cold-prefills. Returns blocks adopted."""
        digests = chain_digests(prompt, self.block_size)
        k_end = offset + len(payloads)
        if k_end > len(digests):
            raise ValueError(
                f"adopt_chain: {len(payloads)} payload blocks at offset "
                f"{offset} overrun the prompt's {len(digests)}-block chain"
            )
        pool = self.scheduler.pool
        st = self.handoff_stats
        run = pool.match_digests(digests[:k_end])
        if run < offset:
            # The sender sliced against a digest summary that has since
            # been evicted here — the graft would have no parent.
            st["adopt_fallbacks"] += 1
            return 0
        m = run  # first position we actually need from the wire
        if m >= k_end:
            st["adopt_skipped_blocks"] += len(payloads)
            return 0
        blocks = pool.alloc(k_end - m)
        if blocks is None:
            st["adopt_fallbacks"] += 1
            return 0
        try:
            nbytes = self._scatter_raw_blocks(blocks, payloads[m - offset:])
            pool.adopt_chain(prompt, blocks, start=m)
        except ValueError:
            pool.free([b for b in blocks if b in pool._allocated])
            raise
        st["adopted"] += 1
        st["adopt_blocks"] += len(blocks)
        st["adopt_bytes"] += nbytes
        st["adopt_skipped_blocks"] += m - offset
        self.scheduler.handoff_bytes_total += nbytes
        return len(blocks)

    def constrain_pool(self, num_blocks: int) -> None:
        """Rebuild the pool with ``num_blocks <= self.num_blocks`` usable
        entries (bench/test hook: sizes the DEVICE pool below a trace's
        prefix working set so eviction/spill pressure is real without a
        tiny HBM budget). Only legal on an idle engine — live requests
        hold block ids the new pool would re-issue. The spill store is
        cleared with the trie."""
        if self.scheduler.active or self.scheduler.pending:
            raise RuntimeError(
                "constrain_pool with requests queued or in flight"
            )
        if not 2 <= num_blocks <= self.num_blocks:
            raise ValueError(
                f"constrain_pool({num_blocks}): need 2 <= n <= "
                f"{self.num_blocks} (the allocated pool)"
            )
        self.scheduler.pool = KVBlockPool(
            num_blocks, self.block_size,
            prefix_cache=self.prefix_cache,
            spill_blocks=self.spill_blocks,
            spill_fn=self._spill_out, drop_fn=self._spill_drop,
        )
        self._spill_store.clear()

    def save_spill_store(self, path: str) -> int:
        """Persist the host spill tier (ledger metadata + payloads) to
        ``path`` — restart-durable warm KV. Device-tier cache and live
        requests are NOT saved; only already-spilled chains survive.
        Returns the number of nodes written."""
        return self.scheduler.pool.save_host_store(
            path, self._spill_store,
            meta={"kv_quant": self.kv_quant,
                  "spill_codec": self.spill_codec},
        )

    def load_spill_store(self, path: str) -> int:
        """Restore a :meth:`save_spill_store` file into this engine's
        host tier: root-connected chains are adopted onto fresh host ids
        (existing hashes win; the ``spill_blocks`` budget caps intake)
        and their payloads installed in the spill store, so subsequent
        admissions match straight through them and promote as usual. The
        file's ``kv_quant``/``spill_codec`` must match this engine's —
        payload bytes are layout-specific. Returns the number of chains
        restored."""
        loaded = self.scheduler.pool.load_host_store(
            path,
            expect_meta={"kv_quant": self.kv_quant,
                         "spill_codec": self.spill_codec},
        )
        self._spill_store.update(loaded)
        return len(loaded)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _sample_body(self, logits, rng, temp, top_k, top_p):
        """Tokens [B] and the advanced ``rng`` [B, 2], through the
        cheapest arm :func:`sampler_arm` allows; the chip runs only the
        taken one. Every arm gives every lane the token the ``filtered``
        arm (the whole of this body before PR 29) would: a greedy lane's
        token is the same argmax in all three; ``_filter_logits`` with
        ``top_k = top_p = 0`` returns its input, so ``plain`` and
        ``filtered`` draw alike where both apply; and a sampling lane's
        ``rng`` row splits in every call that holds it, because a call
        that holds it takes a sampling arm. The calls in which ``greedy``
        left a row unsplit cannot reach a later request: admission
        re-seeds the row (``fold_in(seed, request_id)``, ``_admit_one``),
        and a lane that retires has its ``_temp`` zeroed, so a stale lane
        neither draws nor holds a slow arm."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def draw(rng, filtered):
            tempered = logits / jnp.where(temp > 0, temp, 1.0)[:, None]
            if filtered:
                tempered = _filter_logits(tempered, top_k, top_p)
            split = jax.vmap(jax.random.split)(rng)  # [B, 2, 2]
            sampled = jax.vmap(jax.random.categorical)(split[:, 0], tempered)
            tok = jnp.where(temp > 0, sampled, greedy)
            return tok.astype(jnp.int32), split[:, 1]

        return lax.switch(
            sampler_arm(temp, top_k, top_p),
            (
                lambda rng: (greedy, rng),
                functools.partial(draw, filtered=False),
                functools.partial(draw, filtered=True),
            ),
            rng,
        )

    @staticmethod
    def _expert_load(cache):
        """[expert layers, experts] int32: the tokens this call routed to
        each expert, stacked from the cache's ``expert_load`` leaves in
        flatten (layer) order — or None for a model without experts. It
        leaves the program beside the tokens, so the host reads both in
        the one read-back a step already makes."""
        leaves = [
            leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", None) == _LOAD_LEAF
        ]
        return jnp.stack(leaves) if leaves else None

    def _prefill_fn(self, params, cache, tokens, pos, rng, temp, tk, tp):
        out, cache = prefill(self.model, self._dequant(params), cache, tokens)
        with jax.named_scope("sample"):
            tok, rng = self._sample_body(
                logits_at(out, pos), rng, temp, tk, tp
            )
        return (tok, self._expert_load(cache)), rng, cache

    def _decode_fn(self, params, cache, tok, rng, temp, tk, tp):
        logits, cache = decode_step(
            self.model, self._dequant(params), cache, tok
        )
        with jax.named_scope("sample"):
            tok, rng = self._sample_body(logits, rng, temp, tk, tp)
        return (tok, self._expert_load(cache)), rng, cache

    def _verify_fn(self, params, cache, toks):
        # Greedy-only by construction (the x-sampling fence in submit):
        # no rng / temperature operands, so a lane's PRNG chain is
        # untouched by verify steps.
        greedy, cache = verify_step(
            self.model, self._dequant(params), cache, toks
        )
        return (greedy, self._expert_load(cache)), cache

    def _read_back(self, out):
        """The step's one device read: the tokens a program sampled and,
        for a model with experts, the tokens it routed to each (folded
        into the scheduler's running counts)."""
        tok, load = jax.device_get(out)
        if load is not None:
            self.scheduler.note_expert_load(
                load, getattr(self.model, "held_experts", None)
            )
        return tok

    def _compile(self, fn, *args, name: str | None = None,
                 donate_argnums=()):
        self.num_compiles += 1
        t0 = time.perf_counter()
        with self._tel.span("compile", name=name):
            jitted = jax.jit(fn, donate_argnums=donate_argnums)
            exe = jitted.lower(*args).compile()
        if name is not None:
            # Device registry: compile wall time + memory_analysis(); a
            # second record under one name shows up as recompiles > 0 —
            # the zero-steady-state-recompile contract, visible as data.
            # donated_args counts the donated INPUT LEAVES (the registry's
            # donation counter): > 0 proves the cache pytree aliases
            # input->output instead of double-buffering the KV pool.
            self._tel.record_exe(
                name, exe, compile_s=time.perf_counter() - t0,
                donated_args=sum(
                    len(jax.tree_util.tree_leaves(args[i]))
                    for i in donate_argnums
                ),
            )
        return exe

    def _prefill_exe_for(self, bucket: int):
        exe = self._prefill_exe.get(bucket)
        if exe is None:
            cache1 = self._inject(
                self._cache,
                np.zeros((1, self.pages), np.int32),
                np.zeros((1,), np.int32),
            )
            exe = self._compile(
                self._prefill_fn, self._params, cache1,
                np.zeros((1, bucket), np.int32), np.zeros((1,), np.int32),
                np.zeros((1, 2), np.uint32), np.zeros((1,), np.float32),
                np.zeros((1,), np.int32), np.zeros((1,), np.float32),
                # NOT donated, deliberately: XLA:CPU pairs the [1]-shaped
                # token output with the donated [1]-shaped seq_lens leaf,
                # and that aliasing intermittently returned stale input
                # bytes as the sampled token (garbage/zero first tokens).
                # Decode carries the donation win — it runs every step on
                # the full pool; prefill runs once per request on a B=1
                # slice, so double-buffering it is cheap and correct.
                name=f"serving_prefill_{bucket}",
            )
            self._prefill_exe[bucket] = exe
        return exe

    def _decode_exe_or_compile(self):
        if self._decode_exe is None:
            S = self.slots_n
            cacheS = self._inject(self._cache, self._table, self._lens)
            self._decode_exe = self._compile(
                self._decode_fn, self._params, cacheS,
                np.zeros((S, 1), np.int32), np.zeros((S, 2), np.uint32),
                np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                np.zeros((S,), np.float32),
                name="serving_decode",
                donate_argnums=(1,),  # cache: pool buffers update in place
            )
        return self._decode_exe

    def _verify_exe_or_compile(self):
        if self._verify_exe is None:
            S = self.slots_n
            cacheS = self._inject(self._cache, self._table, self._lens)
            self._verify_exe = self._compile(
                self._verify_fn, self._params, cacheS,
                np.zeros((S, self.spec_k + 1), np.int32),
                name="serving_verify",
                donate_argnums=(1,),  # same in-place pool alias as decode
            )
        return self._verify_exe

    def warmup(self):
        """Compile the decode graph, every prefill width (prompt buckets
        AND suffix buckets — one executable per distinct width, shared
        ``_prefill_exe`` table), and (speculation on) the verify graph
        now, so the serving loop's first requests don't pay compile
        latency (callers run it before any timed window). The
        compile-count pin: ``len(prompt_buckets) + len(suffix_buckets) +
        1`` executables, ``+ 2`` with speculation on — suffix buckets are
        fenced disjoint from prompt buckets, so the arithmetic is exact
        and steady-state traffic of any prompt/suffix mix recompiles
        nothing."""
        with self._tel.span("warmup"):
            self._decode_exe_or_compile()
            if self.spec_k:
                self._verify_exe_or_compile()
            for b in self._prefill_widths:
                self._prefill_exe_for(b)

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------

    def bucket_of(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest "
            f"serving.prompt_buckets entry {self.buckets[-1]}"
        )

    def suffix_bucket_of(self, suffix_len: int) -> int:
        """Smallest prefill width that fits an uncached suffix — drawn
        from suffix buckets AND prompt buckets (one executable per
        distinct width), so a short suffix hits a cheap narrow forward
        while coverage never regresses below the cold path's."""
        for b in self._prefill_widths:
            if suffix_len <= b:
                return b
        raise ValueError(
            f"suffix length {suffix_len} exceeds the largest prefill "
            f"width {self._prefill_widths[-1]}"
        )

    def prefix_match_len(self, prompt: list[int]) -> int:
        """Tokens of ``prompt`` whose KV this replica already caches —
        the read-only trie digest ``router_policy='prefix_affinity'``
        scores candidates with (0 with the cache off: affinity then
        degenerates to least-loaded)."""
        return self.scheduler.pool.match_len(list(prompt))

    def prefix_match_digests(self, digests: list[bytes]) -> int:
        """Cached-token count from PRE-HASHED chain digests
        (``scheduler.chain_digests``) — the router computes the chain
        once per request and probes every replica with it, so probe cost
        is O(prompt) hashing total instead of O(replicas x prompt).
        Matches through the host tier, like admission."""
        return self.scheduler.pool.match_digests(digests) * self.block_size

    def prefix_digest_summary(self, limit: int = 0) -> list[bytes]:
        """The trie digest set (MRU-first, capped at ``limit``) a fleet
        worker ships in its heartbeat — see
        ``KVBlockPool.digest_summary``. Empty with the cache off."""
        return self.scheduler.pool.digest_summary(limit)

    def drain(self) -> None:
        """Graceful shutdown intake cut (the router's elastic-membership
        primitive, docs/SERVING.md): everything already accepted — queued
        AND in-flight — runs to completion exactly as it would have
        (same programs, same tokens), but every new :meth:`submit` is
        rejected by name. Once :meth:`run` reaches idle the pool's
        free list is back to the empty-engine state and the replica can
        be dropped from membership."""
        self.draining = True

    def submit(self, request: Request,
               now: float | None = None) -> RequestState:
        """Enqueue one request. ``now`` overrides the arrival timestamp:
        the ReplicaRouter stamps arrivals with ITS clock — the request
        arrived when it hit the router, not at whatever instant the
        chosen replica's (possibly skewed, possibly virtual) clock
        happens to read."""
        if self.draining:
            raise RuntimeError(
                "ServingEngine is draining: in-flight requests run to "
                "completion but new submissions are rejected — route to "
                "another replica"
            )
        self.bucket_of(len(request.prompt))  # fail before enqueueing
        if self.spec_k and request.temperature > 0:
            # Per-request half of the speculation fence matrix: accepting
            # a greedy-matched prefix under stochastic sampling would skew
            # the sampling distribution (correct rejection sampling over
            # the draft/target distributions is not built).
            raise NotImplementedError(
                "serving.speculation x sampled request (temperature="
                f"{request.temperature}): speculative serving is "
                "greedy-only — submit temperature=0 requests or set "
                "serving.speculation='off'"
            )
        return self.scheduler.submit(
            request, self.clock() if now is None else now
        )

    def _event(self, name: str, state: RequestState, **fields):
        rec = serving_event(
            name, self.step_count,
            request_id=state.request.request_id, **fields,
        )
        self._emit(rec)
        self._tel.note_event(rec)  # flight-recorder mirror

    def _finish_if_done(self, state: RequestState, tok: int) -> bool:
        req = state.request
        done = len(state.generated) >= req.max_new_tokens or (
            self.cfg.eos_id >= 0 and tok == self.cfg.eos_id
        )
        if done:
            slot = state.slot
            self.scheduler.complete(slot, self.clock())
            self._temp[slot] = 0.0
            self._lens[slot] = 0
            self._table[slot] = 0  # park the lane on the null block
            self._table_window[slot] = 0
            self._window_at[slot] = -1
            self._event(
                "request_completed", state,
                new_tokens=len(state.generated),
                slot=slot,
            )
        return done

    def _note_first_token(self, state: RequestState, now: float):
        """First-token bookkeeping (event + TTFT histogram), shared by the
        prefill path and the decode/verify paths — a full-prefix cache hit
        emits its first token from the next BATCHED step, not a prefill."""
        if state.first_token_s is not None:
            return
        state.first_token_s = now
        self._event(
            "first_token", state, slot=state.slot,
            ttft_s=round(now - state.arrival_s, 6),
        )
        # SLO feed: TTFT (arrival -> first token, queueing included) into
        # the mergeable fleet histogram (telemetry.LatencyHistogram) —
        # what the FLEET.json report reads percentiles from.
        self._tel.hist("ttft").record(now - state.arrival_s)

    def _admit_one(self, state: RequestState):
        """One admission (the ``prefill`` span is the caller's):
        ``prefill_prepare`` holds everything before the prefill executable
        is called and ``prefill_readback`` everything from the first host
        read of its outputs on; the dispatch and ``_fold_pools`` between
        them are what is left of ``prefill``."""
        tel = self._tel
        with tel.span("prefill_prepare"):
            req, slot = state.request, state.slot
            # Promote FIRST (before the decode-route branch too — a full-
            # prefix hit can ride through spilled nodes): the device_put
            # inside dispatches async and overlaps everything below, through
            # the suffix-prefill dispatch.
            self._apply_promotions(state)
            row = np.zeros((self.pages,), np.int32)
            chain = state.cached_blocks + state.blocks  # logical block order
            row[: len(chain)] = chain
            rng = np.asarray(
                jax.random.fold_in(
                    jax.random.PRNGKey(self._seed), req.request_id
                ),
                np.uint32,
            )[None]
            # Arm the sampling operands either way; the rng chain starts at
            # the same fold_in(seed, request_id) on every admission path, so
            # tokens are independent of the cache state that admitted them.
            self._table[slot] = row
            if state.window_blocks:
                # The ring as the prompt's END sees it: the prompt's blocks
                # that have left the window by then, and the bucket's
                # padding past its last block, write to the null block.
                with tel.span("window_pages"):
                    self._map_window(
                        state, (len(req.prompt) - 1) // self.block_size
                    )
            self._temp[slot] = req.temperature
            self._top_k[slot] = req.top_k
            self._top_p[slot] = req.top_p
            if state.decode_route:
                if self.role == "prefill":
                    # Full-prefix hit on a PREFILL replica: the entire
                    # exportable chain is already resident, so hand off
                    # without running a forward at all. written=len-1: the
                    # last prompt token's KV was never computed here (no
                    # decode step runs on this role) — the retirement
                    # publish must not cover it.
                    self._queue_handoff(state, written=len(req.prompt) - 1)
                    return
                # Full-prefix hit: every position but the last prompt token is
                # cached, and matching is capped there — so there is nothing
                # to prefill. Arm the lane with the last prompt token as the
                # pending input; the next batched decode/verify step writes
                # its KV (position len-1, in the request's OWN first block)
                # and samples the first new token.
                self._lens[slot] = len(req.prompt) - 1
                self._tok[slot] = req.prompt[-1]
                self._rng[slot] = rng[0]
                return
            off = state.cached_len  # 0 = cold, else suffix-only prefill
            P = state.bucket
            suffix = req.prompt[off:]
            tokens = np.zeros((1, P), np.int32)
            tokens[0, : len(suffix)] = suffix  # RIGHT-padded to the width
            temp = np.float32([req.temperature])
            tk = np.int32([req.top_k])
            tp = np.float32([req.top_p])
            pos = np.int32([len(suffix) - 1])
            arm = SAMPLER_ARMS[int(sampler_arm(temp, tk, tp))]
            exe = self._prefill_exe_for(P)
            # The SAME bulk-prefill body starts at any offset: positions, the
            # causal mask, and the KV scatter all derive from the injected
            # seq_lens leaf, so seq_lens=off shifts everything at once —
            # writes land in the request's own blocks (row[off//bs:]), and the
            # suffix attends to cached prefix KV through the shared table.
            cache1 = self._inject(
                self._cache, row[None], np.int32([off]),
                self._table_window[slot][None],
            )
        out, rng_out, cache1 = exe(
            self._params, cache1, tokens, pos, rng, temp, tk, tp
        )
        self.calls["prefill"] += 1
        self.sampler[arm] += 1
        self._fold_pools(cache1)
        if self.role == "prefill":
            # Prefill-only completion: publish the prompt's blocks (KV
            # written and final) so export_chain sees the whole chain,
            # then queue the handoff instead of arming a decode lane.
            # The token the prefill sampled is DISCARDED, not shipped:
            # the decode replica re-samples it from the same
            # fold_in(seed, request_id) rng chain over the same
            # logits, so greedy (and seeded sampled) output is
            # token-identical to a unified replica — parity by
            # construction, not by trusting the wire.
            self.scheduler.publish_prefix(state, len(req.prompt))
            self._queue_handoff(state, written=len(req.prompt))
            return
        with tel.span("prefill_readback"):
            tok = int(self._read_back(out)[0])
            now = self.clock()
            state.generated.append(tok)
            state.token_times_s.append(now)
            # Arm the lane for decode: the KV holds len real positions (pad
            # writes beyond len are masked and will be overwritten in place).
            self._lens[slot] = len(req.prompt)
            self._tok[slot] = tok
            self._rng[slot] = np.asarray(rng_out[0], np.uint32)
            self._note_first_token(state, now)
            # Publish the prompt's full blocks now that their KV is written
            # and final — later arrivals in the same wave already hit them.
            self.scheduler.publish_prefix(state, len(req.prompt))
            self._finish_if_done(state, tok)

    def step(self) -> bool:
        """One engine iteration: admit (+prefill) into free lanes, then one
        decode call for the whole batch. Returns False when idle."""
        self.step_count += 1
        # The whole iteration, early returns included; its children
        # (schedule, prefill, draft, decode_prepare, decode, collect) leave
        # the gauge block and loop glue as its self time.
        with self._tel.span("engine_step", step=self.step_count):
            return self._step()

    def _step(self) -> bool:
        tel = self._tel
        now = self.clock()
        with tel.span("schedule", step=self.step_count) as sp:
            admitted = self.scheduler.admit(
                now, self.bucket_of, max_admit=self.max_prefills,
                suffix_bucket_of=(
                    self.suffix_bucket_of if self.prefix_cache else None
                ),
                cover_tokens=self.pages * self.block_size,
            )
            if admitted:
                # Request ids discovered inside the span land on its B
                # event (set()), so one request's admission -> prefill ->
                # decode lifecycle is traceable end-to-end in the merged
                # Perfetto view.
                sp.set(request_ids=[s.request.request_id for s in admitted])
        # Kick EVERY admitted state's promote uploads before the first
        # prefill dispatches: the H2D copies run while earlier admissions
        # prefill, instead of each waiting for its own prefill's operand
        # prep.
        for state in admitted:
            self._start_promotions(state)
        for state in admitted:
            extra = {}
            if self.prefix_cache:
                extra["cached_tokens"] = state.cached_len
                # Prefill tokens the trie absorbed for this admission (0
                # on a cold miss) — the per-admission distribution behind
                # the aggregate hit-rate gauge.
                tel.hist("cached_prefill_skip").record(
                    float(state.cached_len)
                )
            self._event(
                "request_admitted", state, slot=state.slot,
                bucket=state.bucket, blocks=len(state.blocks),
                queue_s=round(now - state.arrival_s, 6), **extra,
            )
            tel.hist("queue_wait").record(now - state.arrival_s)
            with tel.span(
                "prefill", step=self.step_count,
                request_id=state.request.request_id, bucket=state.bucket,
            ):
                self._admit_one(state)
        if self.gauge_every and self.step_count % self.gauge_every == 0:
            # Engine-level gauges at a configurable cadence: queue depth
            # and pool occupancy are the capacity-tuning signals
            # (docs/OBSERVABILITY.md), too noisy to emit per request.
            gauges = self.scheduler.gauges(self.clock())
            if self.spec_k and self.spec["drafted"]:
                # Running draft accept rate: the K-tuning signal
                # (docs/TUNING.md) — when it sags, K is paying verify
                # width for tokens that get rejected.
                gauges["spec_accept_rate"] = round(
                    self.spec["draft_hits"] / self.spec["drafted"], 4
                )
            for name, n in self.sampler.items():
                gauges[f"sampler_{name}"] = n  # running counts of calls
            rec = serving_gauges(self.step_count, **gauges)
            self._emit(rec)
            tel.note_event(rec)
            # Gauge digest (last + running max) for the fleet report —
            # queue depth / free blocks are the saturation signals the
            # replica router sheds on.
            tel.note_gauges(gauges)
        active = self.scheduler.active
        if not active:
            return not self.scheduler.idle
        toks = dlens = None
        if self.spec_k:
            with tel.span("draft", step=self.step_count):
                toks = np.zeros((self.slots_n, self.spec_k + 1), np.int32)
                toks[:, 0] = self._tok
                dlens = np.zeros((self.slots_n,), np.int32)
                for state in active:
                    d = self._draft_for(state)
                    if d:
                        toks[state.slot, 1:1 + len(d)] = d
                        dlens[state.slot] = len(d)
        if dlens is not None and dlens.any():
            self._verify_batch(active, toks, dlens)
        else:
            # Speculation off, or no lane found a draft this step: the
            # cheap L=1 program (same tokens either way — verify with an
            # all-empty draft row degenerates to exactly this step).
            self._decode_batch(active)
        return not self.scheduler.idle

    def _decode_operands(self, active, **span_args):
        """The head both batched calls share (the ``decode_prepare``
        span): the cache with this step's table and lengths injected, and
        the arguments of the ``decode`` span that follows."""
        tel = self._tel
        with tel.span("decode_prepare", step=self.step_count):
            if self.window_blocks:
                # What the window layers add to a step on the host: turn
                # the ring of every lane whose cursor entered a new block.
                with tel.span("window_pages"):
                    for state in active:
                        cur = int(self._lens[state.slot]) // self.block_size
                        if cur != self._window_at[state.slot]:
                            self._map_window(state, cur)
            cacheS = self._inject(
                self._cache, self._table, self._lens, self._table_window
            )
            decode_args = {
                "step": self.step_count, "batch": len(active), **span_args
            }
            if tel.enabled:
                # Only materialize the id list when a tracer will keep it.
                decode_args["request_ids"] = [
                    s.request.request_id for s in active
                ]
        return cacheS, decode_args

    def _decode_batch(self, active):
        """One plain decode call (L=1) for the whole batch: the
        non-speculative hot path, and the speculative engine's fallback on
        steps where no lane produced a draft."""
        tel = self._tel
        # The host's copy of the rule, on the arrays the call gets below.
        arm = SAMPLER_ARMS[
            int(sampler_arm(self._temp, self._top_k, self._top_p))
        ]
        cacheS, decode_args = self._decode_operands(
            active, sampler=arm, read_path=self.read_path
        )
        with tel.span("decode", **decode_args):
            out, rng, cacheS = self._decode_exe_or_compile()(
                self._params, cacheS, self._tok[:, None], self._rng,
                self._temp, self._top_k, self._top_p,
            )
            # Sync INSIDE the span: dispatch is async, and the engine
            # blocks on the sampled tokens either way — the decode span
            # must charge for that wait or its histogram flatters L=1 steps
            # relative to the verify path, which must sync to accept.
            tok = self._read_back(out)
        with tel.span("collect", step=self.step_count):
            self.calls["decode"] += 1
            self.sampler[arm] += 1
            self._cache = cacheS
            # np.array (copy): rows must stay writable for the next
            # admission.
            self._rng = np.array(rng, np.uint32)
            now = self.clock()
            for state in active:
                slot = state.slot
                t = int(tok[slot])
                state.generated.append(t)
                state.token_times_s.append(now)
                self._lens[slot] += 1
                self._tok[slot] = t
                self._note_first_token(state, now)  # decode-route admissions
                self._finish_if_done(state, t)

    def _draft_for(self, state: RequestState) -> list[int]:
        """Host-side draft source for one lane (overridable in tests): up
        to ``spec_k`` tokens by n-gram lookup over the request's own
        prompt + generated history."""
        return ngram_draft(
            state.request.prompt + state.generated, self.spec_k
        )

    def _verify_batch(self, active, toks, dlens):
        """One speculative verify call: score all K+1 positions per lane,
        accept each lane's longest greedy-matching draft prefix plus the
        correction/bonus token, and REWIND past rejects by simply not
        advancing the cursor — ``_lens`` is host-authoritative and
        re-injected every call, so KV written for rejected positions is
        dead until the next step's own K+1-position scatter overwrites it
        (the scatter precedes every attention read)."""
        tel = self._tel
        cacheS, decode_args = self._decode_operands(
            active, speculative=True, drafted=int(dlens.sum())
        )
        with tel.span("decode", **decode_args) as sp:
            out, cacheS = self._verify_exe_or_compile()(
                self._params, cacheS, toks
            )
            self.calls["verify"] += 1
            self._cache = cacheS
            greedy = self._read_back(out)
            now = self.clock()
            # Vectorized acceptance: the leading-match run length for
            # every lane in one [S, K] comparison (cumprod of the match
            # mask counts leading Trues), and one bulk int conversion —
            # this loop sits INSIDE the decode span, so per-token python
            # here would eat the very steps speculation just saved.
            runs = np.cumprod(
                toks[:, 1:] == greedy[:, :-1], axis=1
            ).sum(axis=1)
            accepted_toks = greedy.tolist()
            emitted = hits = 0
            for state in active:
                slot = state.slot
                req = state.request
                # Acceptance is clipped so a lane never emits past
                # max_new_tokens — which is also what keeps every
                # ACCEPTED logit's query position inside the lane's block
                # reservation (draft positions beyond it land in the null
                # block and can only feed rejected logits).
                limit = min(
                    int(dlens[slot]),
                    req.max_new_tokens - len(state.generated) - 1,
                )
                m = min(int(runs[slot]), limit)
                acc = accepted_toks[slot][:m + 1]
                # EOS inside an accepted run ends the request THERE, same
                # as the one-token loop would have.
                if self.cfg.eos_id >= 0 and self.cfg.eos_id in acc:
                    acc = acc[: acc.index(self.cfg.eos_id) + 1]
                state.generated.extend(acc)
                state.token_times_s.extend([now] * len(acc))
                self._lens[slot] += len(acc)
                self._tok[slot] = acc[-1]
                self._note_first_token(state, now)  # decode-route admissions
                emitted += len(acc)
                # All-but-the-correction-token were draft hits; after an
                # EOS truncation every remaining token was a hit (the
                # correction token sat past the cut).
                hits += len(acc) - 1 if len(acc) == m + 1 else len(acc)
                tel.hist(SPEC_ACCEPT_HIST).record(float(len(acc)))
                self._finish_if_done(state, acc[-1])
            # Accepted-length span args: the per-step speculation yield,
            # next to the device call in the merged trace view.
            sp.set(accepted=emitted, draft_hits=hits)
        # The acceptance loop sits inside ``decode`` (above), so after a
        # verify call ``collect`` holds the counters alone.
        with tel.span("collect", step=self.step_count):
            self.spec["drafted"] += int(dlens.sum())
            self.spec["draft_hits"] += hits
            self.spec["emitted"] += emitted
            self.spec["lane_steps"] += len(active)

    def run(self, max_steps: int = 0) -> list[RequestState]:
        """Drain the queue; returns the finished states (submit order)."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps and steps >= max_steps:
                break
        return sorted(
            self.scheduler.finished,
            key=lambda s: s.request.request_id,
        )

    def stats(self) -> dict:
        out = {
            **self.scheduler.stats(),
            "num_blocks": self.num_blocks,
            "block_bytes": self.block_bytes,
            "pages_per_seq": self.pages,
            "prompt_buckets": list(self.buckets),
            "suffix_buckets": list(self.suffix_buckets),
            "num_compiles": self.num_compiles,
            "calls": dict(self.calls),
            "sampler": dict(self.sampler),
            "steps": self.step_count,
            "quant": self.quant_report,
            "kv_quant": self.kv_quant,
            "kv_bytes_per_token": self.block_bytes // self.block_size,
            **({"window_block_bytes": self.window_block_bytes}
               if self.window_blocks else {}),
            **self.scheduler.latent_and_expert_gauges(),
            "attn_kernel": self.attn_kernel,
            "read_path": self.read_path,
            "max_prefills_per_step": self.max_prefills,
            "draining": self.draining,
            "speculation": None if not self.spec_k else {
                "k": self.spec_k,
                **self.spec,
                "verify_calls": self.calls["verify"],
                "accept_rate": (
                    round(self.spec["draft_hits"] / self.spec["drafted"], 4)
                    if self.spec["drafted"] else None
                ),
                "mean_accepted_per_step": (
                    round(self.spec["emitted"] / self.spec["lane_steps"], 4)
                    if self.spec["lane_steps"] else None
                ),
            },
        }
        if self.prefix_cache and self.spill_blocks:
            out["prefix_cache"].update({
                "spill_codec": self.spill_codec,
                "spill_store_blocks": len(self._spill_store),
                **self.spill_stats,
            })
        if self.role != "unified":
            out["handoff"] = dict(self.handoff_stats)
        return out
