"""Disaggregated prefill/decode serving: role-split engines, paged
KV-block handoff export/adopt, two-stage router dispatch, and the
cross-process socket fleet — every path pinned EXACTLY against a
unified single-engine oracle (greedy parity by construction: the
prefill side discards its sampled token and the decode side re-seeds
from fold_in(seed, request_id), so who ran the prefill cannot change
the tokens)."""

import dataclasses
import socket

import numpy as np
import pytest

import jax

from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.config import ServingConfig
from distributeddeeplearning_tpu.serving import (
    Request,
    ReplicaRouter,
    ServingEngine,
    SocketReplica,
)
from distributeddeeplearning_tpu.serving import net
from distributeddeeplearning_tpu.serving.router import Replica
from distributeddeeplearning_tpu.serving.worker import ReplicaWorker
from distributeddeeplearning_tpu.telemetry import NULL_TELEMETRY

_CFG = ServingConfig(
    slots=3, block_size=4, hbm_budget_mb=8, max_seq_len=48,
    prompt_buckets=(8, 16), prefix_cache=True, suffix_buckets=(4,),
    router_policy="prefix_affinity",
)
_MAX_NEW = 9


def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return clock


def _cell_clock(t0=100.0):
    t = [t0]
    return t, (lambda: t[0])


@pytest.fixture(scope="module")
def mp():
    model = models.get_model("gpt2", size="tiny", vocab_size=97, max_len=64)
    params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, 8), np.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def prompts():
    # A shared 8-token prefix (2 pool blocks) under varying suffixes,
    # plus one EXACT repeat of prompt 0 — the repeat admits as a full
    # prefix hit on the prefill side, which exercises the decode_route
    # path (handoff written=len(prompt)-1) alongside the prefill path.
    rng = np.random.default_rng(3)
    prefix = list(map(int, rng.integers(1, 97, 8)))
    out = [prefix + list(map(int, rng.integers(1, 97, 2 + i % 5)))
           for i in range(6)]
    out.append(list(out[0]))
    return out


@pytest.fixture(scope="module")
def oracle(mp, prompts):
    model, params = mp
    uni = ServingEngine(model, params, _CFG, clock=_fake_clock())
    for i, p in enumerate(prompts):
        uni.submit(Request(prompt=list(p), max_new_tokens=_MAX_NEW,
                           request_id=i))
    return {s.request.request_id: list(s.generated) for s in uni.run()}


def _engine(mp, role, clock=None, **over):
    model, params = mp
    cfg = dataclasses.replace(_CFG, role=role, **over)
    return ServingEngine(model, params, cfg,
                         clock=clock if clock else _fake_clock())


# ---------------------------------------------------------------------------
# Engine pair: export on one engine, adopt on another, exact parity
# ---------------------------------------------------------------------------


def test_engine_pair_handoff_parity_and_ledger(mp, prompts, oracle):
    pre = _engine(mp, "prefill")
    dec = _engine(mp, "decode")
    for i, p in enumerate(prompts):
        pre.submit(Request(prompt=list(p), max_new_tokens=_MAX_NEW,
                           request_id=i))
    assert pre.run() == []  # a prefill replica never finishes a request
    handoffs = pre.take_handoffs()
    assert len(handoffs) == len(prompts)
    assert pre.scheduler.handoff_queue_depth == 0  # drained
    st = pre.stats()
    assert st["handoff"]["exported"] == len(prompts)
    assert st["finished"] == 0 and st["handed_off"] == len(prompts)
    for h in handoffs:
        req = h["request"]
        # the export always covers the WHOLE prompt chain: the adopt
        # side dedupes, the export side never slices
        assert len(h["payloads"]) == len(h["digests"])
        dec.adopt_chain(req.prompt, h["payloads"])
        dec.submit(Request(prompt=list(req.prompt),
                           max_new_tokens=_MAX_NEW,
                           request_id=req.request_id))
    got = {s.request.request_id: list(s.generated) for s in dec.run()}
    assert got == oracle
    dst = dec.stats()
    # adoption actually warmed the trie: admits ran as prefix hits
    assert dst["prefix_cache"]["hit_tokens"] > 0
    assert dst["handoff"]["adopted"] >= 1
    # the shared prefix shipped once: later chains dedupe against it
    assert dst["handoff"]["adopt_skipped_blocks"] > 0


@pytest.mark.parametrize("sampling", [
    [{"temperature": 0.9}],
    [{"temperature": 1.1, "top_k": 7}, {"temperature": 0.7, "top_p": 0.8}],
    [{}, {"temperature": 0.9}, {"temperature": 1.1, "top_k": 7}],
], ids=["plain", "filtered", "mixed"])
def test_engine_pair_handoff_parity_for_sampled_requests(
        mp, prompts, sampling):
    # The prefill replica draws a sampled request's first token (on a
    # sampling arm of its prefill program) and discards it; the decode
    # replica draws it again from fold_in(seed, request_id), on a lane
    # whose rng row the greedy arm may have left unsplit for any number
    # of calls. Same tokens as one unified engine, sampled or not.
    def requests():
        return [
            Request(prompt=list(p), max_new_tokens=_MAX_NEW, request_id=i,
                    **sampling[i % len(sampling)])
            for i, p in enumerate(prompts)
        ]

    model, params = mp
    uni = ServingEngine(model, params, _CFG, clock=_fake_clock())
    for req in requests():
        uni.submit(req)
    want = {s.request.request_id: list(s.generated) for s in uni.run()}

    pre = _engine(mp, "prefill")
    dec = _engine(mp, "decode")
    by_id = {req.request_id: req for req in requests()}
    for req in by_id.values():
        pre.submit(req)
    assert pre.run() == []
    # A B=1 prefill takes the arm of its own request's kind, no other.
    kinds = {"filtered" if len(kw) > 1 else "plain" if kw else "greedy"
             for kw in sampling}
    arms = pre.stats()["sampler"]
    assert sum(arms.values()) == pre.calls["prefill"] > 0
    assert {k for k, n in arms.items() if n} == kinds
    for h in pre.take_handoffs():
        req = by_id[h["request"].request_id]
        dec.adopt_chain(req.prompt, h["payloads"])
        dec.submit(dataclasses.replace(req))
    got = {s.request.request_id: list(s.generated) for s in dec.run()}
    assert got == want
    for side in (dec, uni):
        arms = side.stats()["sampler"]
        assert (arms["filtered"] > 0) == ("filtered" in kinds)
        assert arms["plain"] > 0 or "filtered" in kinds


def test_adopt_chain_dedupes_stale_slices_and_layout_mismatch(mp, prompts):
    pre = _engine(mp, "prefill")
    dec = _engine(mp, "decode")
    p = prompts[0]
    pre.submit(Request(prompt=list(p), max_new_tokens=_MAX_NEW,
                       request_id=0))
    pre.run()
    (h,) = pre.take_handoffs()
    n = dec.adopt_chain(p, h["payloads"])
    assert n == len(h["payloads"])
    # Re-adopting the same chain is a no-op, not a duplicate graft.
    assert dec.adopt_chain(p, h["payloads"]) == 0
    assert dec.handoff_stats["adopted"] == 1
    # A stale slice — offset beyond what this trie holds — adopts
    # NOTHING and counts a fallback: the request cold-prefills instead
    # of grafting onto a parent that does not exist.
    cold = _engine(mp, "decode")
    assert cold.adopt_chain(p, h["payloads"][2:], offset=2) == 0
    assert cold.handoff_stats["adopt_fallbacks"] == 1
    # Payloads sized for a DIFFERENT pool layout fail by name before
    # any device write.
    with pytest.raises(ValueError, match="layout differs"):
        cold.adopt_chain(p, [b"\x00" * 7 for _ in h["payloads"]])
    # Overrunning the prompt's chain is a caller bug, also by name.
    with pytest.raises(ValueError, match="overrun"):
        cold.adopt_chain(p, h["payloads"], offset=len(h["payloads"]))


def test_scheduler_gauge_shape_back_compat_and_role_fields(mp):
    # A Scheduler built WITHOUT a role (every pre-disaggregation caller,
    # e.g. tests/test_serving_units.py) keeps the exact old gauge shape —
    # no role or handoff keys appear. Engines always pass their role, so
    # heartbeats/FLEET.json see the phase split without new plumbing.
    from distributeddeeplearning_tpu.serving.scheduler import (
        KVBlockPool, Scheduler,
    )

    sched = Scheduler(2, KVBlockPool(8, 4), 32)
    g = sched.gauges(0.0)
    assert "role" not in g
    assert "handoff_queue_depth" not in g
    assert "handoff_bytes_total" not in g

    for role in ("unified", "prefill", "decode"):
        eng = _engine(mp, role)
        eg = eng.scheduler.gauges(0.0)
        assert eg["role"] == role
        assert eg["handoff_queue_depth"] == 0
        assert eg["handoff_bytes_total"] == 0
        # the legacy keys all still ride along
        for key in ("pending", "active", "free_blocks", "used_blocks"):
            assert key in eg


# ---------------------------------------------------------------------------
# Router: two-stage dispatch over in-process replicas
# ---------------------------------------------------------------------------


def test_router_disagg_parity_and_two_stage_dispatch(mp, prompts, oracle):
    clock = _fake_clock()

    def eng(role):
        return _engine(mp, role, clock=clock)

    transports = [
        Replica(index=0, engine=eng("prefill"), telemetry=NULL_TELEMETRY),
        Replica(index=1, engine=eng("decode"), telemetry=NULL_TELEMETRY),
        Replica(index=2, engine=eng("decode"), telemetry=NULL_TELEMETRY),
    ]
    router = ReplicaRouter(None, None, _CFG, clock=clock,
                           transports=transports)
    assert router.roles == ["prefill", "decode", "decode"]
    for i, p in enumerate(prompts):
        router.submit(Request(prompt=list(p), max_new_tokens=_MAX_NEW,
                              request_id=i))
    got = {s.request.request_id: list(s.generated)
           for s in router.run()}
    assert got == oracle
    st = router.stats()
    assert st["roles"] == ["prefill", "decode", "decode"]
    assert st["handoffs"] == len(prompts)
    # stage 1 admitted every request to the prefill replica; stage 2
    # landed every chain on a DECODE replica, which is where the final
    # route (and the tokens) live
    assert all(router.routes[i] in (1, 2) for i in range(len(prompts)))
    pre_stats = transports[0].engine.stats()
    assert pre_stats["handoff"]["exported"] == len(prompts)
    assert pre_stats["finished"] == 0
    assert sum(t.engine.stats()["handoff"]["adopted"]
               for t in transports[1:]) >= 1


# ---------------------------------------------------------------------------
# Socket fleet: role in hello, KV frames on the wire, multi-part chains
# ---------------------------------------------------------------------------


def _socket_fleet(mp, roles, cfg, clock):
    model, params = mp
    workers, transports = [], []
    for i, role in enumerate(roles):
        rs, ws = socket.socketpair()
        rs.setblocking(False)
        ws.setblocking(False)
        eng = ServingEngine(model, params,
                            dataclasses.replace(cfg, role=role),
                            clock=clock)
        eng.warmup()
        w = ReplicaWorker(eng, ws, replica_index=i, clock=clock,
                          sleep=lambda s: None,
                          heartbeat_interval_s=cfg.heartbeat_interval_s,
                          telemetry=NULL_TELEMETRY)
        w.start()
        dec = net.FrameDecoder()
        frames = net.recv_available(rs, dec) or []
        assert frames and frames[0]["type"] == "hello"
        assert frames[0]["role"] == role
        transports.append(SocketReplica(i, rs, frames[0], clock=clock,
                                        decoder=dec, backlog=frames[1:]))
        workers.append(w)
    router = ReplicaRouter(None, None, cfg, clock=clock,
                           transports=transports)
    return workers, router


def _drive(workers, router, t, prompts):
    for i, p in enumerate(prompts):
        router.submit(Request(prompt=list(p), max_new_tokens=_MAX_NEW,
                              request_id=i))
    for _ in range(8000):
        if router.idle:
            break
        t[0] += 0.01
        for w in workers:
            if w.exit_code is None:
                w.pump()
        router.step()
    else:
        raise AssertionError("fleet never drained idle")
    return {s.request.request_id: list(s.generated)
            for s in router.finished()}


def test_socket_fleet_disagg_parity(mp, prompts, oracle):
    cfg = dataclasses.replace(_CFG, heartbeat_interval_s=0.05,
                              heartbeat_timeout_s=0.0)
    t, clock = _cell_clock()
    workers, router = _socket_fleet(mp, ["prefill", "decode", "decode"],
                                    cfg, clock)
    assert _drive(workers, router, t, prompts) == oracle
    st = router.stats()
    assert st["roles"] == ["prefill", "decode", "decode"]
    assert st["handoffs"] == len(prompts)
    assert st["handoff_parts"] >= len(prompts)
    pre = workers[0].engine.stats()
    assert pre["handoff"]["exported"] == len(prompts)
    assert pre["finished"] == 0
    assert sum(w.engine.stats()["handoff"]["adopted"]
               for w in workers[1:]) >= 1


def test_socket_fleet_multipart_handoff_parity(mp, prompts, oracle):
    # One block per KV frame: every chain ships as multiple parts, only
    # the LAST part triggers the decode-side submit, and the sticky
    # (request_id, epoch) route keeps all parts on one replica. Tokens
    # must not notice.
    cfg = dataclasses.replace(_CFG, heartbeat_interval_s=0.05,
                              heartbeat_timeout_s=0.0,
                              handoff_blocks_per_frame=1)
    t, clock = _cell_clock()
    workers, router = _socket_fleet(mp, ["prefill", "decode", "decode"],
                                    cfg, clock)
    assert _drive(workers, router, t, prompts) == oracle
    st = router.stats()
    assert st["handoffs"] == len(prompts)
    assert st["handoff_parts"] > st["handoffs"]
