"""The plain reference and the comparison that decides ``correct``."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import check  # noqa: E402
import graphgen  # noqa: E402
import harness  # noqa: E402

ref = harness.load_module(BENCH, "configs", "simrank_power")


def _dense_simrank(src, dst, n, c, iters):
    deg = np.bincount(dst, minlength=n)
    w = np.zeros((n, n))
    for s, d in zip(src, dst):
        w[s, d] += 1.0 / deg[d]
    s_ = np.eye(n)
    for _ in range(iters):
        s_ = c * w.T @ s_ @ w
        np.fill_diagonal(s_, 1.0)
    return s_


@pytest.mark.parametrize("block_bytes", [4096, 1 << 28])
def test_power_method_matches_a_dense_one(block_bytes):
    n = 120
    src, dst = graphgen.generate(n, 700, 2.5, seed=4)
    src = np.concatenate([src, src[:5]])  # repeated edges count per copy
    dst = np.concatenate([dst, dst[:5]])
    want = _dense_simrank(src, dst, n, 0.6, 15)
    got = np.asarray(ref.simrank(src, dst, n, c=0.6, iterations=15,
                                 block_bytes=block_bytes))
    assert np.abs(got - want).max() < 1e-6
    rows = ref.rows(src, dst, n, [3, 0, 3], c=0.6, iterations=15)
    assert sorted(rows) == [0, 3] and np.abs(rows[3] - want[3]).max() < 1e-6


def test_iterations_for_the_tolerance():
    k = ref.iterations_for(0.6, 1e-5)
    assert 0.6 ** (k + 1) <= 1e-5 < 0.6 ** k


def _answer(truth, u, k, noise=0.0, rng=None):
    t = truth.copy()
    t[u] = -np.inf
    nodes = np.argsort(-t, kind="stable")[:k]
    est = t[nodes] + (noise * rng.standard_normal(k) if noise else 0.0)
    order = np.argsort(-est, kind="stable")
    return ({"node": u}, {"node": u, "version": 0, "error_bound": 0.1,
                          "topk_nodes": nodes[order].tolist(),
                          "topk_scores": est[order].tolist()})


def test_exact_answers_read_zero_and_wrong_ones_do_not():
    n = 100
    src, dst = graphgen.generate(n, 600, 2.5, seed=8)
    rows = ref.rows(src, dst, n, [1, 2], c=0.6, iterations=22)
    truth = {(0, u): r for u, r in rows.items()}
    good = [_answer(rows[u], u, 10) for u in (1, 2)]
    for req, body in good:
        assert check.well_formed(req, body, n=n, k=10)
    nums = check.compare(good, truth, walks=1000, eps_a=0.1)
    assert nums["z_max"] < 1e-3 and nums["rank_z"] < 1e-3
    assert nums["z_rms"] <= nums["z_max"]
    assert nums["err_over_bound"] < 1e-6 and nums["bound_over_eps"] == 1.0
    bad_req, bad = _answer(rows[1], 1, 10)
    outside = [v for v in range(n) if v not in bad["topk_nodes"] and v != 1]
    bad["topk_nodes"][0] = min(outside, key=lambda v: rows[1][v])
    worse = check.compare([(bad_req, bad)], truth, walks=1000, eps_a=0.1)
    assert worse["z_max"] > 10 and worse["rank_z"] > 1
    assert worse["z_rms"] > 10 / np.sqrt(10)


@pytest.mark.parametrize("field,value", [
    ("topk_nodes", [1] * 10), ("topk_scores", [0.5] + [0.9] * 9),
    ("error_bound", None), ("version", -1), ("node", 7)])
def test_malformed_answers(field, value):
    n = 50
    truth = np.linspace(0, 0.2, n)
    req, body = _answer(truth, 3, 10)
    assert check.well_formed(req, body, n=n, k=10)
    body[field] = value
    assert not check.well_formed(req, body, n=n, k=10)
