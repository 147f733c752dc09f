"""Hierarchical ICI+DCN gradient collectives (comms_hier.py;
docs/MULTISLICE.md).

Contracts pinned here:
- the index math: intra/cross replica groups and the chunk permutation
  ``pi(i) = (i % ici) * dcn + i // ici`` (a bijection — member i owns global
  chunk pi(i) after intra-then-cross reduce-scatter);
- the fp32 decomposition against a NUMPY oracle, BITWISE: XLA CPU's flat
  psum is the left fold over members; the hierarchical psum is the fold
  within each slice then across slices — same sum, re-associated;
- training parity: hierarchical == flat losses on the same mesh (fp32,
  incl. bucketed + fused K-step), sharded == replicated under hierarchy,
  quantized wire formats within codec tolerance;
- the HLO shape of the acceptance criteria: ICI-sub-group reduce-scatter +
  all-gather carrying the full bucket payload, a cross-slice all-reduce
  carrying exactly payload/ici, and NO dp-spanning collective left with a
  gradient-sized payload;
- the ``cli launch`` plan (coordinator env threading, device pinning,
  prefixed streaming) as pure functions;
- (slow, version-gated) a REAL 2-process dp=4/dcn_dp=2 run matching the
  single-process dp=4 oracle.
"""

import io
import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import helpers
from distributeddeeplearning_tpu import comms_hier as ch
from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.train import Trainer, get_task, make_optimizer

N = 8
DCN = 2
TOPO = ch.HierTopology(n=N, dcn=DCN)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Topology index math
# ---------------------------------------------------------------------------


def test_topology_groups():
    assert TOPO.ici == 4
    assert TOPO.intra_groups() == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert TOPO.cross_groups() == ((0, 4), (1, 5), (2, 6), (3, 7))


def test_chunk_permutation_is_a_bijection():
    perm = [TOPO.chunk_index(i) for i in range(N)]
    assert sorted(perm) == list(range(N))
    # Member (d, j) ends with global chunk j*dcn + d: slice-local position
    # picks the intra chunk, slice id the cross sub-chunk within it.
    assert perm == [0, 2, 4, 6, 1, 3, 5, 7]


def test_rings_stay_within_their_level():
    # Quantized path: the intra ring never leaves a slice, the cross ring
    # never changes slice-local position.
    for src, dst in TOPO.intra_perm():
        assert src // TOPO.ici == dst // TOPO.ici
    for src, dst in TOPO.cross_perm():
        assert src % TOPO.ici == dst % TOPO.ici


def test_resolve_hierarchy_modes():
    assert ch.resolve_hierarchy("auto", 1) is False
    assert ch.resolve_hierarchy("auto", 2) is True
    assert ch.resolve_hierarchy("flat", 4) is False
    assert ch.resolve_hierarchy("hierarchical", 2) is True
    with pytest.raises(ValueError, match="comm_hierarchy"):
        ch.resolve_hierarchy("fastest", 2)


# ---------------------------------------------------------------------------
# fp32 collectives vs a numpy oracle (bitwise)
# ---------------------------------------------------------------------------


def _sm(fn, mesh):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P("dp", None),), out_specs=P("dp", None),
        check_vma=False,
    )


def _left_fold(arrs):
    acc = arrs[0].copy()
    for a in arrs[1:]:
        acc = acc + a
    return acc


@pytest.fixture(scope="module")
def hier_data():
    mesh = helpers.mesh_of(dp=N)
    rng = np.random.default_rng(0)
    data = (rng.standard_normal((N, 512)) * 10).astype(np.float32)
    return mesh, data


def test_hier_psum_matches_slice_fold_oracle_bitwise(hier_data):
    mesh, data = hier_data
    flat = np.asarray(_sm(lambda x: jax.lax.psum(x[0], "dp")[None], mesh)(data))
    hier = np.asarray(
        _sm(lambda x: ch.hier_psum(x[0], "dp", TOPO)[None], mesh)(data)
    )
    ici = TOPO.ici
    slice_sums = [
        _left_fold([data[d * ici + j] for j in range(ici)])
        for d in range(DCN)
    ]
    # XLA CPU reduces in member order: flat == one left fold, hier == the
    # fold within each slice then across slices. Both checks are BITWISE —
    # the decomposition is exact, only the association differs.
    assert np.array_equal(flat[0], _left_fold([data[i] for i in range(N)]))
    assert np.array_equal(hier[0], _left_fold(slice_sums))
    # Replicated across every member, and numerically the same sum.
    assert all(np.array_equal(hier[i], hier[0]) for i in range(N))
    np.testing.assert_allclose(hier[0], flat[0], rtol=1e-5)


def test_hier_psum_scatter_places_permuted_chunks_bitwise(hier_data):
    mesh, data = hier_data
    shards = np.asarray(
        _sm(lambda x: ch.hier_psum_scatter(x[0], "dp", TOPO)[None], mesh)(data)
    )
    hier = np.asarray(
        _sm(lambda x: ch.hier_psum(x[0], "dp", TOPO)[None], mesh)(data)
    )[0]
    chunk = data.shape[1] // N
    for i in range(N):
        c = TOPO.chunk_index(i)
        assert np.array_equal(shards[i], hier[c * chunk:(c + 1) * chunk]), i


def test_hier_scatter_then_gather_round_trips_bitwise(hier_data):
    mesh, data = hier_data

    def rt(x):
        s = ch.hier_psum_scatter(x[0], "dp", TOPO)
        return ch.hier_all_gather(s, "dp", TOPO)[None]

    gathered = np.asarray(_sm(rt, mesh)(data))
    hier = np.asarray(
        _sm(lambda x: ch.hier_psum(x[0], "dp", TOPO)[None], mesh)(data)
    )
    assert np.array_equal(gathered, hier)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_hier_quantized_all_reduce_replicated_and_close(hier_data, mode):
    mesh, data = hier_data
    exact = np.asarray(
        _sm(lambda x: jax.lax.psum(x[0], "dp")[None], mesh)(data)
    )[0]
    q = np.asarray(_sm(
        lambda x: ch.hier_quantized_all_reduce_flat(
            x[0], "dp", TOPO, mode=mode, block_size=64
        )[None],
        mesh,
    )(data))
    assert all(np.array_equal(q[i], q[0]) for i in range(N))
    rel = np.abs(q[0] - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel


# ---------------------------------------------------------------------------
# Training parity (the tentpole's numeric acceptance)
# ---------------------------------------------------------------------------


def _assert_losses_within_ulps(got, want, maxulp=4):
    """Per-step float32 losses ``maxulp`` apart at most (observed: 0, 0, 0,
    1 on jax 0.9.0's XLA:CPU)."""
    np.testing.assert_array_max_ulp(
        np.float32(got), np.float32(want), maxulp=maxulp
    )


def test_train_parity_hier_equals_flat_fp32():
    mesh = helpers.mesh_of(dp=N)
    flat, _ = helpers.train_tiny_gpt2(mesh, n_steps=4)
    hier, _ = helpers.train_tiny_gpt2(
        mesh, n_steps=4, dcn_dp=DCN, comm_hierarchy="hierarchical"
    )
    # NOT the same math: flat sums every gradient element over 8 members in
    # one all-reduce, the hierarchy over 4 (intra-slice reduce-scatter) and
    # then over 2 (cross-slice all-reduce). The fp32 sums are re-associated,
    # so params already differ in their last bits after ONE step (~40 % of
    # elements after four) and the losses can only agree to a few ulps. The
    # decomposition itself is proven bitwise against the numpy oracle above.
    _assert_losses_within_ulps(hier, flat)


def test_train_parity_hier_bucketed_and_fused_ksteps():
    # Bucketed sync + the fused K-step scan, both under the hierarchy —
    # the full composition surface of the acceptance criterion.
    mesh = helpers.mesh_of(dp=N)
    ds = data_lib.SyntheticTokens(
        batch_size=16, seq_len=32, vocab_size=256, seed=0, n_distinct=4
    )
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=256, max_len=64, dropout_rate=0.0
    )

    def run(**kw):
        tr = Trainer(
            model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
            donate=False, grad_bucket_mb=0.05, **kw,
        )
        state = tr.init(0, ds.batch(0))
        step = tr.fused_train_step(2)
        losses = []
        it = data_lib.sharded_superbatches(ds.iter_from(0), mesh, 2)
        for _ in range(2):
            state, metrics = step(state, next(it))
            losses.extend(float(v) for v in np.asarray(metrics["loss"]))
        return losses

    flat = run()
    hier = run(dcn_dp=DCN, comm_hierarchy="auto")
    # Flat against hierarchy: the same re-associated gradient sum as above.
    _assert_losses_within_ulps(hier, flat)


_SAME_MATH_WORKER = """
import hashlib, json, sys
import jax, numpy as np
sys.path.insert(0, "tests")
import helpers

mesh = helpers.mesh_of(dp=8)
kw = dict(n_steps=4, dcn_dp=2, comm_hierarchy="auto", grad_bucket_mb=0.05)
out = {}
for name, extra in (
    ("replicated", {}), ("sharded", {"update_sharding": "sharded"})
):
    losses, state = helpers.train_tiny_gpt2(mesh, **kw, **extra)
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(state.params):
        digest.update(np.asarray(leaf).tobytes())
    out[name] = {
        "losses": [float(x).hex() for x in losses],
        "params": digest.hexdigest(),
    }
print("RESULT", json.dumps(out))
"""


def test_train_parity_sharded_equals_replicated_under_hier():
    # The intra-slice reduce-scatter doubles as the shard split: member i
    # updates global chunk pi(i), the two-phase gather reassembles — the
    # update must be the SAME math as the replicated hierarchy, bitwise, in
    # every loss and every final param.
    #
    # No reduction differs between the two (after one step the Adam moments
    # are bit-equal); what differs on jax 0.9.0's XLA:CPU is instruction
    # selection: LLVM contracts ``b1*mu + (1-b1)*g`` into an FMA in one
    # program's update fusion and not in the other's (flat 1/N shards there,
    # per-leaf shapes here), so from step 2 on ``mu`` rounds differently.
    # Bitwise is therefore asserted where no FMA exists: a child process
    # held to plain AVX. (``xla_cpu_max_isa`` is read once per process, not
    # per compile, hence the child.)
    from distributeddeeplearning_tpu.utils.compat import set_cpu_device_env

    env = dict(os.environ)
    set_cpu_device_env(env, N)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=AVX"
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SAME_MATH_WORKER],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(
        next(
            line for line in proc.stdout.splitlines()
            if line.startswith("RESULT")
        )[len("RESULT "):]
    )
    assert out["sharded"] == out["replicated"], out


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_train_hier_quantized_wire_stays_close(mode):
    # Quantize-once composition: EF residuals keyed per bucket as on the
    # flat path; the hierarchical rings move only compressed payloads.
    mesh = helpers.mesh_of(dp=N)
    fp32, _ = helpers.train_tiny_gpt2(mesh, n_steps=3)
    q, _ = helpers.train_tiny_gpt2(
        mesh, n_steps=3, dcn_dp=4, comm_hierarchy="auto", grad_comm=mode,
        grad_bucket_mb=0.05,
    )
    assert all(np.isfinite(q))
    np.testing.assert_allclose(q, fp32, rtol=1e-3)


def test_hier_residual_schema_matches_flat():
    # The EF residual state must keep the flat path's schema (one [dp,
    # padded] row-stack per bucket) so checkpoints and zero.residual_
    # shardings are hierarchy-agnostic.
    mesh = helpers.mesh_of(dp=N)
    _, s_flat = helpers.train_tiny_gpt2(
        mesh, n_steps=1, grad_comm="int8", grad_bucket_mb=0.05
    )
    _, s_hier = helpers.train_tiny_gpt2(
        mesh, n_steps=1, grad_comm="int8", grad_bucket_mb=0.05,
        dcn_dp=DCN, comm_hierarchy="auto",
    )
    flat_shapes = [r.shape for r in s_flat.grad_residual]
    hier_shapes = [r.shape for r in s_hier.grad_residual]
    assert flat_shapes == hier_shapes
    assert all(r.shape[0] == N for r in s_hier.grad_residual)


# ---------------------------------------------------------------------------
# HLO obligations (ISSUE acceptance): ICI-sub-group RS + AG, cross-slice AR
# of exactly payload/ici, no gradient-sized dp-spanning collective
# ---------------------------------------------------------------------------

_HLO_CACHE: dict = {}


def _hlo(**trainer_kw):
    key = tuple(sorted(trainer_kw.items()))
    if key not in _HLO_CACHE:
        mesh = helpers.mesh_of(dp=N)
        model = models.get_model(
            "gpt2", size="tiny", vocab_size=256, max_len=64,
            dropout_rate=0.0, attn_impl="xla", mesh=None,
        )
        ds = data_lib.SyntheticTokens(
            batch_size=16, seq_len=32, vocab_size=256, seed=0, n_distinct=4
        )
        tr = Trainer(
            model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh,
            donate=False, **trainer_kw,
        )
        text = helpers.compiled_step_text(tr, ds.batch(0), mesh, spmd=True)
        _HLO_CACHE[key] = (text, tr._layout)
    return _HLO_CACHE[key]


def test_hlo_hier_step_structure():
    text, layout = _hlo(dcn_dp=DCN, comm_hierarchy="hierarchical")
    total = layout.padded_sizes[0] * 4  # one bucket, fp32 bytes
    ici = TOPO.ici
    # Intra-slice reduce-scatter + all-gather carry the FULL payload over
    # ICI groups (RS payloads are normalized to full-input bytes).
    assert total in helpers.group_payloads(text, N, "reduce-scatter", ici)
    assert total in helpers.group_payloads(text, N, "all-gather", ici)
    # The cross-slice all-reduce carries EXACTLY payload/ici — the only
    # DCN-crossing gradient traffic.
    assert total // ici in helpers.group_payloads(text, N, "all-reduce", DCN)
    # Replica-group membership, not just group size: RS/AG stay within a
    # slice; the AR spans one member per slice.
    intra = frozenset(frozenset(g) for g in TOPO.intra_groups())
    cross = frozenset(frozenset(g) for g in TOPO.cross_groups())
    assert intra in helpers.replica_group_sets(text, "reduce-scatter")
    assert intra in helpers.replica_group_sets(text, "all-gather")
    assert cross in helpers.replica_group_sets(text, "all-reduce")
    # No gradient-sized dp-spanning collective remains: everything on the
    # full-dp group is scalar metrics traffic.
    for kind in ("all-reduce", "reduce-scatter", "all-gather",
                 "collective-permute"):
        leftovers = [
            p for p in helpers.dp_group_payloads(text, N, kind)
            if p >= total // ici
        ]
        assert not leftovers, (kind, leftovers)


def test_hlo_hier_bucketed_per_bucket_decomposition():
    # Each bucket decomposes independently: K intra reduce-scatters whose
    # normalized payloads ARE the bucket partition, and K cross all-reduces
    # at exactly 1/ici of each.
    text, layout = _hlo(
        dcn_dp=DCN, comm_hierarchy="hierarchical", grad_bucket_mb=0.05
    )
    assert layout.num_buckets >= 3
    ici = TOPO.ici
    want = sorted(p * 4 for p in layout.padded_sizes)
    rs = [p for p in helpers.group_payloads(text, N, "reduce-scatter", ici)
          if p >= min(want)]
    assert sorted(rs) == want
    ars = [p for p in helpers.group_payloads(text, N, "all-reduce", DCN)
           if p >= min(want) // ici]
    assert sorted(ars) == sorted(p // ici for p in want)


def test_hlo_flat_control_has_no_subgroup_collectives():
    # comm_hierarchy='flat' on the same mesh: the gradient sync is ONE
    # full-dp collective; no ICI/DCN sub-group traffic appears.
    text, layout = _hlo(dcn_dp=DCN, comm_hierarchy="flat")
    total = layout.padded_sizes[0] * 4 if layout is not None else 0
    for kind in ("all-reduce", "reduce-scatter", "all-gather"):
        for group in (TOPO.ici, DCN):
            assert not helpers.group_payloads(text, N, kind, group), (
                kind, group
            )
    if total:
        assert total in helpers.dp_group_payloads(text, N, "all-reduce")


# ---------------------------------------------------------------------------
# cli launch (plan + prefix streaming as pure functions)
# ---------------------------------------------------------------------------


def test_launch_plan_threads_coordinator_env():
    from distributeddeeplearning_tpu import cli

    plan = cli._launch_plan(
        "cfg.py", ["a.b=1"], 2, devices_per_process=2,
        coordinator_port=12345, base_env={"KEEP": "me"},
    )
    assert len(plan) == 2
    for pid, (cmd, env) in enumerate(plan):
        assert cmd[:5] == [
            sys.executable, "-m", "distributeddeeplearning_tpu.cli",
            "train", "--config",
        ]
        assert "a.b=1" in cmd and "--override" in cmd
        assert env["COORDINATOR_ADDRESS"] == "localhost:12345"
        assert env["NUM_PROCESSES"] == "2"
        assert env["PROCESS_ID"] == str(pid)
        # Fleet-telemetry stamp: every child knows its index even before
        # jax distributed init (telemetry.resolve_process_index reads it).
        assert env["DDL_PROCESS_INDEX"] == str(pid)
        assert env["KEEP"] == "me"
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["JAX_NUM_CPU_DEVICES"] == "2"
    assert plan[0][0] == plan[1][0]  # same command, env differs per process


def test_launch_plan_defaults():
    from distributeddeeplearning_tpu import cli

    plan = cli._launch_plan("c.py", [], 3, base_env={})
    # No device pinning unless asked (real hosts discover their own), and
    # one shared auto-picked coordinator port.
    addrs = set()
    for _, env in plan:
        assert "JAX_NUM_CPU_DEVICES" not in env
        addrs.add(env["COORDINATOR_ADDRESS"])
    assert len(addrs) == 1
    port = int(addrs.pop().rsplit(":", 1)[1])
    assert 0 < port < 65536


def test_launch_plan_independent_mode():
    # --independent: N uncoordinated single-process children sharing one
    # telemetry dir — the fleet-observability rehearsal mode on jax builds
    # whose CPU backend has no multiprocess rendezvous. No coordinator
    # env (each child is its own world); the process stamp still set.
    from distributeddeeplearning_tpu import cli

    plan = cli._launch_plan(
        "cfg.py", [], 2, devices_per_process=2,
        base_env={"PROCESS_ID": "7", "COORDINATOR_ADDRESS": "stale:1"},
        independent=True,
    )
    assert len(plan) == 2
    for pid, (_cmd, env) in enumerate(plan):
        assert env["DDL_PROCESS_INDEX"] == str(pid)
        # Inherited coordination env is scrubbed, not leaked: a stale
        # PROCESS_ID would both misconfigure jax and mis-stamp telemetry.
        assert "COORDINATOR_ADDRESS" not in env
        assert "NUM_PROCESSES" not in env
        assert "PROCESS_ID" not in env
        assert env["JAX_NUM_CPU_DEVICES"] == "2"


def test_launch_plan_rejects_single_process():
    from distributeddeeplearning_tpu import cli

    with pytest.raises(ValueError, match="num-processes"):
        cli._launch_plan("c.py", [], 1)


def test_stream_prefixed_attributes_every_line():
    from distributeddeeplearning_tpu import cli

    src = io.StringIO('step 1\n{"event": "save"}\n')
    out = io.StringIO()
    cli._stream_prefixed(src, "[p3] ", out)
    assert out.getvalue() == '[p3] step 1\n[p3] {"event": "save"}\n'


# ---------------------------------------------------------------------------
# Multiprocess CPU backend: dp=4 over 2 processes with dcn_dp=2 vs the
# single-process dp=4 oracle (slow lane; version-gated like test_fault_
# tolerance's rendezvous test)
# ---------------------------------------------------------------------------

_MP = dict(vocab=128, max_len=64, seq=32, batch=8, lr=1e-3, steps=2)


def _mp_train_losses(mesh, **trainer_kw):
    """The training body both topologies run (same seeds, same data) —
    ONE definition, imported by the worker subprocess below, so oracle and
    multiprocess runs cannot drift apart."""
    model = models.get_model(
        "gpt2", size="tiny", vocab_size=_MP["vocab"], max_len=_MP["max_len"]
    )
    trainer = Trainer(
        model, make_optimizer("adamw", _MP["lr"]), get_task("lm"), mesh,
        donate=False, **trainer_kw,
    )
    ds = data_lib.SyntheticTokens(
        batch_size=_MP["batch"], seq_len=_MP["seq"], vocab_size=_MP["vocab"]
    )
    state = trainer.init(0, ds.batch(0))
    losses = []
    for i, batch in enumerate(data_lib.sharded_batches(ds.iter_from(0), mesh)):
        if i >= _MP["steps"]:
            break
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


_MP_WORKER = """
import sys
import jax
from distributeddeeplearning_tpu.mesh import MeshConfig, build_mesh, init_distributed

addr, pid = sys.argv[1], int(sys.argv[2])
assert init_distributed(addr, 2, pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()

sys.path.insert(0, "tests")
import test_hier

mesh = build_mesh(MeshConfig(dp=4, dcn_dp=2))
losses = test_hier._mp_train_losses(
    mesh, dcn_dp=2, comm_hierarchy="hierarchical"
)
print("LOSSES", losses)
"""


@pytest.mark.slow
def test_two_process_hier_matches_single_process():
    """dp=4 split as 2 processes x 2 devices (each process one simulated
    slice), hierarchical sync on — the launcher-shaped topology — must
    match the single-process dp=4 flat run within fp32 tolerance."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    addr = f"localhost:{port}"
    from distributeddeeplearning_tpu.utils.compat import set_cpu_device_env

    env = dict(os.environ)
    set_cpu_device_env(env, 2)  # 2 procs x 2 = 4 global devices
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MP_WORKER, addr, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=540) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-3000:]
    import ast

    losses = [
        ast.literal_eval(
            next(
                line for line in out.splitlines()
                if line.startswith("LOSSES")
            )[len("LOSSES "):]
        )
        for out, _ in outs
    ]
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert all(np.isfinite(losses[0]))
    oracle = _mp_train_losses(helpers.mesh_of(dp=4))
    np.testing.assert_allclose(losses[0], oracle, rtol=1e-5)
