"""The traffic generator: the same requests for the same seed, others for
another, and the same multiset of lengths for every seed."""

import json
import os

from benchmarks.harness import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def _spec(name="closed128_sharegpt"):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_requests_other_seed_others():
    a = traffic.RequestSource(_spec(), 2**31 + 5, 50257)
    b = traffic.RequestSource(_spec(), 2**31 + 5, 50257)
    c = traffic.RequestSource(_spec(), 2**31 + 6, 50257)
    for i in (0, 1, 77, 1500):
        assert a.request(i, first=True) == b.request(i, first=True)
        assert a.request(i) == b.request(i)
    assert [a.request(i).prompt for i in range(8)] != [
        c.request(i).prompt for i in range(8)
    ]


def test_every_seed_holds_the_same_sizes_and_other_tokens():
    a = traffic.RequestSource(_spec(), 1, 50257)
    c = traffic.RequestSource(_spec(), 2, 50257)
    la = [a.lengths(i) for i in range(traffic.POOL)]
    lc = [c.lengths(i) for i in range(traffic.POOL)]
    assert la == lc  # the seed does not change the work
    assert a.request(5).prompt != c.request(5).prompt
    # every cycle holds the same (prompt, output) pairs, in another order
    nxt = [a.lengths(i) for i in range(traffic.POOL, 2 * traffic.POOL)]
    assert nxt != la and sorted(nxt) == sorted(la)
    fa = [a.lengths(i, first=True) for i in range(traffic.POOL)]
    assert fa == [c.lengths(i, first=True) for i in range(traffic.POOL)]
    assert sum(o for _, o in fa) < sum(o for _, o in la)
    plens = sorted(p for p, _ in la)
    assert plens[0] >= 16 and plens[-1] <= 512
    # the source's means (161.31 in, 337.99 out), less what the clips take
    assert 150 <= sum(plens) / len(plens) <= 162
    assert 255 <= sum(o for _, o in la) / len(la) <= 275
    for p, o in la:
        assert p + o <= 1024  # no operation fails: fits max_seq_len


def test_open_loop_schedules():
    spec = {"kind": "poisson", "rate": 50.0}
    t1 = traffic.arrival_times(spec, 7, 20.0)
    assert t1 == traffic.arrival_times(spec, 7, 20.0)
    assert t1 != traffic.arrival_times(spec, 8, 20.0)
    assert 800 < len(t1) < 1200 and t1 == sorted(t1) and t1[-1] < 20.0
    burst = traffic.arrival_times({"kind": "burst", "rate": 50.0, "burst": 10}, 7, 20.0)
    assert len(burst) % 10 == 0 and 700 < len(burst) < 1300
    assert traffic.arrival_times({"kind": "closed", "clients": 4}, 7, 20.0) == []
