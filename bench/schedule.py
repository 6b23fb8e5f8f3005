"""Arrival schedules and update bursts, made from the run's seed.

Queries arrive as a Poisson process conditioned on its count: exactly
``round(rate * seconds)`` arrival times, uniform over the window and
sorted, so every seed offers the same amount of work in another order.
Each query names a source drawn uniformly from the given nodes and a
``seed`` that pins its random walks, so an answer is reproducible.

Update bursts (``updates`` in the traffic file) come every ``period_s``.
A burst inserts ``inserts`` edges whose destinations follow the
configuration's in-degree law and whose sources are uniform, skipping
self-loops and live edges, and deletes the ``deletes`` oldest live edges
(the base edges in the order they were generated, then the inserted ones
in the order they were made), a TTL window that keeps ``m`` constant.
"""
from __future__ import annotations

from collections import deque

import numpy as np

SEED_CAP = 2**31 - 1  # request seeds fit a signed 32-bit integer


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent stream per use, all from the run's seed."""
    return np.random.default_rng([int(seed) % 2**63, stream])


def query_body(traffic: dict, node: int, seed: int) -> dict:
    q = traffic["queries"]
    return {"kind": q["kind"], "node": int(node), "k": int(q["k"]),
            "seed": int(seed)}


def queries(traffic: dict, seconds: float, seed: int, sources,
            rate: float | None = None) -> list[dict]:
    """The window's query requests as ``{"t", "path", "body"}``."""
    rng = rng_for(seed, 1)
    rate = traffic["queries"]["rate_per_s"] if rate is None else rate
    count = int(round(rate * seconds))
    times = np.sort(rng.uniform(0.0, seconds, count))
    nodes = rng.choice(np.asarray(sources), count)
    seeds = rng.integers(0, SEED_CAP, count)
    return [{"t": float(t), "path": "/query",
             "body": query_body(traffic, u, s)}
            for t, u, s in zip(times, nodes, seeds)]


def warmup_query(traffic: dict, seed: int, sources) -> dict:
    rng = rng_for(seed, 2)
    return query_body(traffic, rng.choice(np.asarray(sources)),
                      rng.integers(0, SEED_CAP))


class Churn:
    """The update stream over a live edge set (host bookkeeping only)."""

    def __init__(self, src, dst, n: int, dst_weights, seed: int,
                 inserts: int, deletes: int):
        self.n = int(n)
        self.inserts, self.deletes = int(inserts), int(deletes)
        self.p = np.asarray(dst_weights, np.float64)
        keys = np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)
        self.live = set(keys.tolist())
        self.age = deque(keys.tolist())  # oldest first
        self.rng = rng_for(seed, 3)

    def burst(self) -> dict:
        """The next burst's body: ``{"inserts": [...], "deletes": [...]}``."""
        n, new = self.n, []
        chosen = set()
        while len(new) < self.inserts:
            want = 2 * (self.inserts - len(new)) + 8
            d = self.rng.choice(n, want, p=self.p)
            s = self.rng.integers(0, n, want)
            for a, b in zip(s.tolist(), d.tolist()):
                k = a * n + b
                if a == b or k in self.live or k in chosen:
                    continue
                chosen.add(k)
                new.append(k)
                if len(new) == self.inserts:
                    break
        gone = [self.age.popleft() for _ in range(self.deletes)]
        for k in gone:
            self.live.discard(k)
        for k in new:
            self.live.add(k)
            self.age.append(k)
        return {"inserts": [[k // n, k % n] for k in new],
                "deletes": [[k // n, k % n] for k in gone]}


def updates(traffic: dict, seconds: float, churn: Churn) -> list[dict]:
    """The window's update requests, one burst every ``period_s``."""
    u = traffic.get("updates")
    if not u:
        return []
    period = float(u["period_s"])
    count = int(seconds / period + 1e-9)
    return [{"t": j * period, "path": "/update", "body": churn.burst()}
            for j in range(count)]
