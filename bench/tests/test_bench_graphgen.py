"""The benchmark's graph generator: exact, simple and seed-determined."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import graphgen  # noqa: E402


@pytest.mark.parametrize("n,m,exponent", [(300, 2400, 2.5), (1000, 9000, 3.0)])
def test_exact_n_m_no_loops_no_duplicates(n, m, exponent):
    src, dst = graphgen.generate(n, m, exponent, seed=5)
    assert len(src) == len(dst) == m
    assert src.min() >= 0 and dst.min() >= 0 and max(src.max(), dst.max()) < n
    assert not (src == dst).any()
    assert len(np.unique(src.astype(np.int64) * n + dst)) == m


def test_deterministic_from_the_seed():
    a = graphgen.generate(500, 4000, 2.5, seed=2**31 + 17)
    b = graphgen.generate(500, 4000, 2.5, seed=2**31 + 17)
    c = graphgen.generate(500, 4000, 2.5, seed=2**31 + 18)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()


def test_every_seed_has_the_same_degree_sequence():
    seqs = [np.sort(np.bincount(graphgen.generate(400, 3000, 3.0, s)[1],
                                minlength=400)) for s in (1, 2, 3)]
    assert all((s == seqs[0]).all() for s in seqs)
    assert seqs[0].sum() == 3000


def test_degree_sequence_follows_the_weights():
    d = graphgen.degree_sequence(34546, 421578, 3.0)
    w = graphgen.degree_weights(34546, 421578, 3.0)
    assert d.sum() == 421578 and (np.abs(d - w) < 1).all()
    assert d[0] == 1139  # the hub of cit-hepph's configuration


def test_stats_report_the_realized_graph():
    src, dst = graphgen.generate(300, 2400, 2.5, seed=1)
    s = graphgen.stats(src, dst, 300, k_max=200, capacity=2500)
    deg = np.bincount(dst, minlength=300)
    assert s["m"] == 2400 and s["max_in_degree"] == deg.max()
    assert s["mean_in_degree"] == pytest.approx(8.0)
    assert s["ell_bytes"] == 300 * 200 * 4 and s["coo_bytes"] == 2500 * 8


def test_weights_by_node_match_the_degrees():
    src, dst = graphgen.generate(300, 2400, 2.5, seed=3)
    p = graphgen.in_degree_weights_by_node(300, 2400, 2.5, dst)
    deg = np.bincount(dst, minlength=300)
    assert p.sum() == pytest.approx(1.0)
    assert p[deg.argmax()] == p.max()


def test_only_targets_take_in_edges():
    src, dst = graphgen.generate(700, 10000, 2.5, seed=9, targets=280)
    deg = np.bincount(dst, minlength=700)
    assert (deg > 0).sum() == 280 and deg.sum() == 10000
    assert not (src == dst).any()
    assert len(np.unique(src.astype(np.int64) * 700 + dst)) == 10000
    p = graphgen.in_degree_weights_by_node(700, 10000, 2.5, dst, targets=280)
    assert (p[deg == 0] == 0).all() and p.sum() == pytest.approx(1.0)
