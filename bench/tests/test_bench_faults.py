"""A run whose timed path is broken underneath reads ``correct = false``.

Each test drives a whole run of a tiny copy of a cell on the CPU (the
harness's look for a chip skipped) with one fault planted in the program:
an answer altered where it is produced, a serve step that returns its
state unchanged, half of each batch left out, and an update that is
acknowledged but leaves the graph unchanged.  (The cells run on one chip,
so there is no exchange between chips to leave out.)
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import tiny  # noqa: E402

SECONDS = 3.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # a rate above the tiny service's capacity, so dispatches run full
    return tiny.make(tmp_path_factory.mktemp("bench"), rate=30.0)


def _run(root, workload, seed=2**31 + 5):
    return harness.run(workload, seed, SECONDS, False, root=root,
                       require_chip=False, cache=False)


def _patch_topk(monkeypatch, fault):
    from repro.api import backend

    orig = backend.multi_source_topk

    def broken(key, g, eg, us, k, params, **kw):
        idx, vals = orig(key, g, eg, us, k, params, **kw)
        idx, vals = np.array(idx), np.array(vals)
        return fault(np.asarray(us), idx, vals, g.n)

    monkeypatch.setattr(backend, "multi_source_topk", broken)


def altered(us, idx, vals, n):
    row = set(idx[0].tolist())
    idx[0, 0] = next(v for v in range(n) if v not in row and v != us[0])
    return idx, vals


def unchanged_state(us, idx, vals, n):
    # the probe accumulated nothing: every estimate 0, so top-k returns
    # the lowest node ids other than the source, with score 0
    for q, u in enumerate(us):
        idx[q] = [v for v in range(n) if v != u][: idx.shape[1]]
    return idx, np.zeros_like(vals)


def half_batch(us, idx, vals, n):
    h = len(us) // 2
    idx[h:], vals[h:] = idx[: len(us) - h], vals[: len(us) - h]
    return idx, vals


@pytest.mark.parametrize("fault", [altered, unchanged_state, half_batch],
                         ids=lambda f: f.__name__)
def test_serve_faults_are_not_correct(root, monkeypatch, fault):
    _patch_topk(monkeypatch, fault)
    # the churn cell's tiny copy reads correct when sound; the hepph copy,
    # cut to 300 nodes that are all cited, does not (PERF.md, section 6)
    r = _run(root, "wikivote-churn")
    assert r["correct"] is False, r["checks"]


def test_update_acknowledged_but_not_applied_is_not_correct(root, monkeypatch):
    import jax.numpy as jnp

    from repro.api.handle import GraphHandle

    monkeypatch.setattr(GraphHandle, "apply_batch",
                        lambda self, batch: jnp.ones(batch.size, bool))
    r = _run(root, "wikivote-churn")
    assert r["correct"] is False
    assert r["checks"]["edge_diff"]["value"] > 0
