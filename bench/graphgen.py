"""The benchmark's own graph generator (host, numpy).

A directed graph with exactly the published ``n`` and ``m``, no self-loops
and no duplicate edges, made from a seed.  In-degrees follow Chung-Lu
weights ``w_i ~ i^(-1/(exponent-1))`` over the first ``targets`` ranks
(every node by default; the other nodes take no in-edges, as the voters
of a vote graph who never stood for election), scaled to sum to ``m``; the weights
are rounded to an integer in-degree sequence that sums to ``m`` exactly
(largest remainders first), so every seed gives the same degree sequence,
the same maximum in-degree and therefore the same device shapes.  The seed
decides which node takes which degree and where each edge comes from:
sources are uniform over the other nodes, and a source that would repeat
an edge or close a self-loop is drawn again until none does.  The edge
list comes back in a seeded random order, which is the order a TTL window
deletes them in.
"""
from __future__ import annotations

import numpy as np


def degree_weights(n: int, m: int, exponent: float,
                   targets: int | None = None) -> np.ndarray:
    """Chung-Lu in-degree weights by rank (descending), summing to ``m``;
    ranks past ``targets`` weigh 0."""
    t = n if targets is None else int(targets)
    w = np.zeros(n, np.float64)
    w[:t] = np.arange(1, t + 1, dtype=np.float64) ** (-1.0 / (exponent - 1.0))
    return w * (m / w.sum())


def degree_sequence(n: int, m: int, exponent: float,
                    targets: int | None = None) -> np.ndarray:
    """Integer in-degrees by rank that sum to ``m`` exactly."""
    w = degree_weights(n, m, exponent, targets)
    d = np.floor(w).astype(np.int64)
    short = m - int(d.sum())
    # largest fractional parts first; a stable sort breaks ties by rank
    order = np.argsort(-(w - d), kind="stable")
    order = order[w[order] > 0]  # a rank that weighs 0 takes no edge
    d[order[:short]] += 1
    if d.max() > n - 1:
        raise ValueError(f"in-degree {d.max()} needs more than {n - 1} sources")
    return d


def generate(n: int, m: int, exponent: float, seed: int,
             targets: int | None = None):
    """``(src, dst)`` int32 arrays of ``m`` distinct non-loop edges."""
    rng = np.random.default_rng([int(seed) % 2**63, 0x6E])
    d = degree_sequence(n, m, exponent, targets)
    indeg = np.zeros(n, np.int64)
    indeg[rng.permutation(n)] = d
    dst = np.repeat(np.arange(n, dtype=np.int64), indeg)
    src = rng.integers(0, n, m)
    bad = np.ones(m, bool)
    while bad.any():
        src[bad] = rng.integers(0, n, int(bad.sum()))
        key = src * n + dst
        _, first = np.unique(key, return_index=True)
        bad = np.ones(m, bool)
        bad[first] = False  # later copies of a pair are redrawn
        bad |= src == dst
    order = rng.permutation(m)
    return src[order].astype(np.int32), dst[order].astype(np.int32)


def in_degree_weights_by_node(n: int, m: int, exponent: float, dst,
                              targets: int | None = None) -> np.ndarray:
    """Each node's Chung-Lu weight as a probability, matched to the node
    that took that rank's degree in ``dst`` (highest in-degree first)."""
    w = degree_weights(n, m, exponent, targets)
    indeg = np.bincount(dst, minlength=n)
    rank_of_node = np.empty(n, np.int64)
    rank_of_node[np.argsort(-indeg, kind="stable")] = np.arange(n)
    p = w[rank_of_node]
    return p / p.sum()


def stats(src, dst, n: int, *, k_max: int, capacity: int) -> dict:
    """The realized graph as the run reports it."""
    indeg = np.bincount(dst, minlength=n)
    return dict(
        n=int(n),
        m=int(len(src)),
        max_in_degree=int(indeg.max()),
        mean_in_degree=float(indeg.mean()),
        nodes_with_in_edges=int((indeg > 0).sum()),
        ell_bytes=int(n) * int(k_max) * 4,
        coo_bytes=int(capacity) * 2 * 4,
    )
