"""Arrival schedules and update bursts are fixed by the seed."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import graphgen  # noqa: E402
import schedule  # noqa: E402

TRAFFIC = {"queries": {"kind": "topk", "k": 10, "rate_per_s": 7.5},
           "updates": {"period_s": 0.2, "inserts": 8, "deletes": 8}}


def test_queries_deterministic_with_a_fixed_count():
    a = schedule.queries(TRAFFIC, 20.0, 3_000_000_000, range(50))
    b = schedule.queries(TRAFFIC, 20.0, 3_000_000_000, range(50))
    c = schedule.queries(TRAFFIC, 20.0, 3_000_000_001, range(50))
    assert a == b and a != c
    assert len(a) == len(c) == 150  # same work for every seed
    times = [r["t"] for r in a]
    assert times == sorted(times) and 0 <= times[0] and times[-1] < 20.0
    assert {r["path"] for r in a} == {"/query"}
    assert all(0 <= r["body"]["seed"] < 2**31 and r["body"]["k"] == 10
               for r in a)


def test_rate_override_and_sources():
    reqs = schedule.queries(TRAFFIC, 10.0, 1, [4, 9], rate=2.0)
    assert len(reqs) == 20 and {r["body"]["node"] for r in reqs} <= {4, 9}


def _churn(seed):
    n, m = 200, 1500
    src, dst = graphgen.generate(n, m, 2.5, seed=1)
    p = graphgen.in_degree_weights_by_node(n, m, 2.5, dst)
    return src, dst, n, schedule.Churn(src, dst, n, p, seed, 8, 8)


def test_bursts_keep_m_and_delete_the_oldest_first():
    src, dst, n, churn = _churn(7)
    live = set((src.astype(np.int64) * n + dst).tolist())
    reqs = schedule.updates(TRAFFIC, 2.0, churn)
    assert [r["t"] for r in reqs] == [j * 0.2 for j in range(10)]
    for j, r in enumerate(reqs):
        ins = [s * n + d for s, d in r["body"]["inserts"]]
        dels = [s * n + d for s, d in r["body"]["deletes"]]
        assert len(ins) == len(set(ins)) == 8 and len(dels) == 8
        assert not set(ins) & live and all(k // n != k % n for k in ins)
        base = (src.astype(np.int64) * n + dst)[8 * j: 8 * j + 8].tolist()
        assert dels == base  # base edges go in generation order
        live -= set(dels)
        live |= set(ins)
        assert len(live) == 1500


def test_bursts_deterministic():
    a = schedule.updates(TRAFFIC, 1.0, _churn(9)[3])
    b = schedule.updates(TRAFFIC, 1.0, _churn(9)[3])
    c = schedule.updates(TRAFFIC, 1.0, _churn(10)[3])
    assert a == b and a != c


def test_no_updates_without_an_update_stream():
    assert schedule.updates({"updates": None}, 5.0, None) == []
