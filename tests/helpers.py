"""Shared test helpers: mesh construction over device subsets and the
tiny-GPT-2 parity train loop every parallelism-strategy test reuses
(SURVEY §4 tier 2 — the single template, not per-file copies)."""

from __future__ import annotations

import math
import os
import re
import subprocess
import sys

import jax

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.mesh import MeshConfig, build_mesh
from distributeddeeplearning_tpu.train import Trainer, get_task, make_optimizer


_TOPO_PROBE: dict = {}


def topology_available(topology: str = "v5e:2x2", timeout: int = 90) -> bool:
    """One cached probe per pytest run: ``get_topology_desc`` can HANG
    rather than raise in containers whose libtpu probes a live backend at
    topology-description time — an in-process try/except cannot catch that,
    so the AOT-topology tests would wedge the whole suite. Probe it in a
    killable subprocess instead."""
    if topology not in _TOPO_PROBE:
        code = (
            "from jax.experimental import topologies\n"
            "topologies.get_topology_desc("
            f"platform='tpu', topology_name={topology!r})\n"
        )
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, timeout=timeout,
            )
            _TOPO_PROBE[topology] = proc.returncode == 0
        except subprocess.TimeoutExpired:
            _TOPO_PROBE[topology] = False
    return _TOPO_PROBE[topology]


def skip_unless_topology(topology: str = "v5e:2x2") -> None:
    import pytest

    if not topology_available(topology):
        pytest.skip(
            f"deviceless TPU topology {topology!r} unavailable: "
            "get_topology_desc hangs or fails in this environment "
            "(probed in a subprocess)"
        )


def mesh_of(**axes):
    """Mesh over exactly prod(axes) of the simulated devices — lets a test
    exercise e.g. a pure tp=2 mesh without padding dp to absorb the rest."""
    n = math.prod(axes.values())
    axes.setdefault("dp", 1)
    return build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def compiled_step_text(trainer, example_batch, mesh, *, spmd: bool = False):
    """Compile ``trainer.train_step`` abstractly (ShapeDtypeStructs with the
    real batch sharding — no data materialized) and return HLO text.

    ``spmd=False``: the fully optimized backend module — what actually runs.
    ``spmd=True``: the module as the SPMD partitioner emitted it (dumped via
    per-compile ``xla_dump_hlo_pass_re``), BEFORE backend float
    normalization. That is the honest view of collective payload dtypes:
    the CPU sim's float-support pass promotes bf16 all-reduces to f32
    (``_promoted`` regions in the optimized text) because CPU has no native
    bf16 arithmetic, while a TPU build keeps them bf16 — so mixed-precision
    byte assertions must read this stage. Shared by test_grad_comm,
    test_precision and test_hlo_bytes instead of per-file copies.
    """
    import glob
    import shutil
    import tempfile

    lowered = trainer.lower_train_step(example_batch)
    if not spmd:
        return lowered.compile().as_text()
    dump = tempfile.mkdtemp(prefix="ddl_hlo_dump_")
    # The persistent compile cache (conftest) would satisfy this compile
    # without running any pass — and an executable fetched from cache dumps
    # nothing. Dump options are scrubbed from the cache key, so a prior
    # plain compile of the same program — even from an EARLIER pytest run,
    # the cache dir is cross-process — silently starves the dump; disable
    # the cache for this one compile. Flipping the config flag alone is not
    # enough: jax initializes its cache object exactly once per process and
    # keeps serving it afterwards, so drop that object too (reset_cache)
    # and let it lazily re-initialize as disabled / re-enabled.
    from jax._src import compilation_cache as _cc

    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()
        lowered.compile(
            {"xla_dump_to": dump, "xla_dump_hlo_pass_re": "spmd"}
        )
        paths = glob.glob(os.path.join(dump, "*after_spmd-partitioning*"))
        assert len(paths) == 1, (
            f"expected exactly one post-partitioner dump, got {paths}"
        )
        with open(paths[0]) as f:
            return f.read()
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _cc.reset_cache()
        shutil.rmtree(dump, ignore_errors=True)


def sync_wire_bytes(text: str, n: int) -> float:
    """Ring-model per-member wire bytes of the dp-group collectives.
    Robust to the CPU SPMD emitter's op choices (e.g. reduce-scatter
    lowered as all-reduce + dynamic-slice) because it totals over kinds."""
    from distributeddeeplearning_tpu.utils.hlo import collective_bytes

    factors = {"all-reduce": 2 * (n - 1) / n, "collective-permute": 1.0}
    total = 0.0
    for kind, entries in collective_bytes(text, n).items():
        for payload, group in entries:
            if group >= n // 2:
                total += factors.get(kind, (n - 1) / n) * payload
    return total


def dp_group_payloads(text: str, n: int, kind: str) -> list[int]:
    """Sorted payload bytes of every full-dp-group collective of ``kind``
    in HLO text. Scalar/control collectives (metric psums, health-guard
    flags) ride along in any step program — callers threshold on payload
    to separate them from gradient traffic."""
    from distributeddeeplearning_tpu.utils.hlo import collective_bytes

    return sorted(p for p, g in collective_bytes(text, n).get(kind, ()) if g == n)


def group_payloads(text: str, n: int, kind: str, group: int) -> list[int]:
    """Sorted payload bytes of every ``kind`` collective whose replica
    groups have exactly ``group`` members — the hierarchy tests' view of
    sub-axis collectives (``group == n`` reproduces dp_group_payloads)."""
    from distributeddeeplearning_tpu.utils.hlo import collective_bytes

    return sorted(
        p for p, g in collective_bytes(text, n).get(kind, ()) if g == group
    )


def replica_group_sets(text: str, kind: str) -> list[frozenset[frozenset[int]]]:
    """The explicit replica-group partition of every ``kind`` collective in
    HLO text, as a set of member sets — what the hierarchy HLO tests pin:
    intra-slice groups ``{{0..ici-1}, ...}`` vs cross-slice groups
    ``{{0, ici, ...}, ...}`` (docs/MULTISLICE.md)."""
    out = []
    pat = re.compile(
        rf"{kind}(?:-start)?\(.*replica_groups=\{{(\{{[0-9,]+\}}"
        rf"(?:,\{{[0-9,]+\}})*)\}}"
    )
    for line in text.splitlines():
        m = pat.search(line)
        if m:
            out.append(frozenset(
                frozenset(int(x) for x in grp.split(","))
                for grp in re.findall(r"\{([0-9,]+)\}", m.group(1))
            ))
    return out


def entry_schedule(text: str, *, min_payload: int) -> tuple[list[int], list[int]]:
    """Schedule-order view of the OPTIMIZED module's ENTRY computation:
    ``(all_reduce_lines, compute_lines)`` — line indices of all-reduces
    carrying at least ``min_payload`` bytes and of compute ops (fusions /
    dots / convolutions). The CPU backend prints the entry computation in
    its final thunk schedule order, so "compute lines between the first and
    last gradient all-reduce" is exactly the overlap window the bucketed
    sync path exists to open (docs/OVERLAP.md)."""
    from distributeddeeplearning_tpu.utils.hlo import _OP_LINE, _type_bytes

    entry: list[str] = []
    inside = False
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            inside = True
            continue
        if inside:
            if line.startswith("}"):
                break
            entry.append(line)
    assert entry, "no ENTRY computation found in HLO text"
    ar_lines, compute_lines = [], []
    compute = re.compile(r"= .* (fusion|dot|convolution)(\.[0-9]+)?\(")
    for i, line in enumerate(entry):
        m = _OP_LINE.search(line)
        if m and m.group("kind") == "all-reduce":
            payload = _type_bytes(m.group("type"), start_op=bool(m.group("start")))
            if payload >= min_payload:
                ar_lines.append(i)
        elif compute.search(line):
            compute_lines.append(i)
    return ar_lines, compute_lines


def train_tiny_gpt2(
    mesh,
    *,
    attn_impl: str = "xla",
    rules=None,
    n_steps: int = 5,
    batch_size: int = 16,
    seq_len: int = 32,
    dtype=None,
    **trainer_kw,
):
    """Train the tiny GPT-2 for ``n_steps`` on synthetic tokens; returns
    (per-step losses, final TrainState). Deterministic in everything except
    the mesh/sharding, which is what parity tests compare across.

    ``dtype`` sets the model compute dtype (the precision tests pair it with
    ``precision="bf16"``, mirroring what cli.build_all derives from the
    config); a ``precision`` trainer kwarg is forwarded to make_optimizer
    too, so bf16_full gets its low-precision moment transform."""
    model_kw = {}
    if dtype is not None:
        model_kw["dtype"] = dtype
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=256, max_len=64, dropout_rate=0.0,
        attn_impl=attn_impl,
        mesh=mesh if attn_impl in ("ring", "ring_pallas") else None,
        **model_kw,
    )
    ds = data_lib.SyntheticTokens(
        batch_size=batch_size, seq_len=seq_len, vocab_size=256, seed=0,
        n_distinct=4,
    )
    kw = dict(donate=False)
    if rules is not None:
        kw["rules"] = rules
    kw.update(trainer_kw)
    opt = make_optimizer(
        "adamw", 1e-3, precision=kw.get("precision", "fp32")
    )
    trainer = Trainer(model, opt, get_task("lm"), mesh, **kw)
    state = trainer.init(0, ds.batch(0))
    losses = []
    for i, batch in enumerate(data_lib.sharded_batches(ds, mesh)):
        if i >= n_steps:
            break
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state
