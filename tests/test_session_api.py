"""Session API (repro.api): parity with the legacy surface + invariants.

The tentpole contracts:

* ``SimRankSession.query`` is BIT-IDENTICAL to the legacy core entry points
  (``single_source`` / ``topk`` / ``multi_source_topk``) under shared PRNG
  keys — the session is a new surface, not a new estimator;
* ``drain()`` reproduces the PR-1 engine's fused dispatch exactly
  (submit-time streams, repeat-padded batches);
* ``GraphHandle`` update/regrow invariants (mirror == rebuild, sticky
  overflow, version accounting) hold when driven through the session;
* the §4.4 planner resolves ``variant='auto'`` to a concrete legacy
  variant (never a new code path);
* the legacy engines and ``single_source_simple`` are deprecation shims
  that match their pre-session behavior.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    GraphHandle,
    QuerySpec,
    ResultEnvelope,
    SimRankSession,
    abs_error_bound,
)
from repro.core import (
    make_params,
    multi_source,
    multi_source_topk,
    single_source,
    single_source_simple,
    topk,
)
from repro.graph import (
    ell_from_edges,
    erdos_renyi_graph,
    graph_from_edges,
    graph_to_host_edges,
    powerlaw_graph,
)


def _mirrors_equal_rebuild(h: GraphHandle):
    """COO and ELL mirrors bit-identical to a from-scratch rebuild."""
    src, dst = h.to_host_edges()
    g_rb = graph_from_edges(src, dst, h.n, capacity=h.capacity)
    eg_rb = ell_from_edges(src, dst, h.n, k_max=h.k_max)
    np.testing.assert_array_equal(np.asarray(h.g.src), np.asarray(g_rb.src))
    np.testing.assert_array_equal(np.asarray(h.g.dst), np.asarray(g_rb.dst))
    np.testing.assert_array_equal(np.asarray(h.g.in_deg), np.asarray(g_rb.in_deg))
    np.testing.assert_array_equal(np.asarray(h.eg.in_nbrs), np.asarray(eg_rb.in_nbrs))
    np.testing.assert_array_equal(np.asarray(h.eg.in_deg), np.asarray(eg_rb.in_deg))


@pytest.fixture()
def toy_handle(toy):
    return GraphHandle(g=toy["g"], eg=toy["eg"])


@pytest.fixture()
def toy_session(toy_handle):
    return SimRankSession(
        toy_handle, c=0.25, eps_a=0.1, top_k=3, batch_q=2, seed=0,
        walk_chunk=64,
    )


# ---------------------------------------------------------------------------
# GraphHandle
# ---------------------------------------------------------------------------


def test_handle_from_edges_matches_mirror_pair(toy):
    """from_edges == the legacy graph_from_edges + ell_from_edges pair."""
    h = GraphHandle.from_edges(toy["src"], toy["dst"], toy["n"])
    np.testing.assert_array_equal(np.asarray(h.g.src), np.asarray(toy["g"].src))
    np.testing.assert_array_equal(
        np.asarray(h.eg.in_nbrs), np.asarray(toy["eg"].in_nbrs)
    )
    assert h.n == toy["n"] and h.version == 0 and not h.overflow
    assert h.num_edges == int(toy["g"].num_edges)


def test_handle_rejects_mismatched_mirrors(toy):
    src, dst, n = toy["src"], toy["dst"], toy["n"]
    other = ell_from_edges(src, dst, n + 1)
    with pytest.raises(ValueError):
        GraphHandle(g=toy["g"], eg=other)


# ---------------------------------------------------------------------------
# query(): bit-parity with the legacy core entry points under shared keys
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["telescoped", "tree"])
def test_query_single_source_parity(toy_session, toy, key, variant):
    sess = toy_session
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01)
    env = sess.query(
        QuerySpec(kind="single_source", node=0, key=key, variant=variant)
    )
    ref = np.asarray(single_source(
        key, toy["g"], toy["eg"], 0, params, variant=variant, walk_chunk=64
    ))
    assert np.array_equal(env.scores, ref)  # bit-for-bit
    assert env.kind == "single_source" and env.variant == variant
    assert env.version == 0 and env.walks_used == params.n_r


def test_query_topk_parity(toy_session, toy, key):
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01)
    env = toy_session.query(
        QuerySpec(kind="topk", node=0, k=3, key=key, variant="telescoped")
    )
    idx, vals = topk(
        key, toy["g"], toy["eg"], 0, 3, params, variant="telescoped",
        walk_chunk=64,
    )
    assert np.array_equal(env.topk_nodes, np.asarray(idx))
    assert np.array_equal(env.topk_scores, np.asarray(vals))
    assert 0 not in env.topk_nodes  # query node excluded


def test_query_batched_parity(toy_session, toy, key):
    """Batched specs == multi_source(_topk): scalar key splits (legacy
    semantics), a [Q] key array passes through as per-query streams."""
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01)
    us = jnp.asarray([0, 2, 4], jnp.int32)
    env = toy_session.query(QuerySpec(kind="topk", nodes=(0, 2, 4), k=3, key=key))
    idx, vals = multi_source_topk(key, toy["g"], toy["eg"], us, 3, params, lanes=64)
    assert np.array_equal(env.topk_nodes, np.asarray(idx))
    assert np.array_equal(env.topk_scores, np.asarray(vals))

    keys = jax.random.split(key, 3)
    env2 = toy_session.query(
        QuerySpec(kind="single_source", nodes=(0, 2, 4), key=keys)
    )
    est = multi_source(None, toy["g"], toy["eg"], us, params, lanes=64, keys=keys)
    assert np.array_equal(env2.scores, np.asarray(est))


def test_drain_reproduces_fused_engine_dispatch(small_powerlaw):
    """submit/drain == the PR-1 engine formula: fold_in(seed, seq) streams,
    fixed-size batches through multi_source_topk; the final short batch's
    live query owns every lane column, so it equals that query alone."""
    g, eg, n = small_powerlaw["g"], small_powerlaw["eg"], small_powerlaw["n"]
    h = GraphHandle(g=g, eg=eg)
    qs = np.argsort(-np.asarray(g.in_deg))[:3].astype(int)  # 3 qs, batch_q=2
    sess = SimRankSession(h, c=0.6, eps_a=0.2, top_k=5, batch_q=2, seed=7,
                          walk_chunk=64)
    for u in qs:
        sess.submit(int(u))
    res = sess.drain(budget_walks=96)
    assert [r.node for r in res] == list(qs)

    params = make_params(n, c=0.6, eps_a=0.2, delta=0.01)
    streams = [jax.random.fold_in(jax.random.key(7), i) for i in range(3)]
    b0 = multi_source_topk(
        None, g, eg, jnp.asarray(qs[:2], jnp.int32), 5, params,
        lanes=64, n_r=96, keys=jnp.stack(streams[:2]),
    )
    b1 = multi_source_topk(  # final short batch: its live query alone
        None, g, eg, jnp.asarray([qs[2]], jnp.int32), 5, params,
        lanes=64, n_r=96, keys=jnp.stack([streams[2]]),
    )
    assert np.array_equal(res[0].topk_scores, np.asarray(b0[1])[0])
    assert np.array_equal(res[1].topk_scores, np.asarray(b0[1])[1])
    assert np.array_equal(res[2].topk_scores, np.asarray(b1[1])[0])
    assert sess.stats.queries == 3 and sess.stats.steps == 2


def test_short_batch_reports_its_lanes(small_powerlaw):
    """A drained batch's answers report the lane columns their query
    owned: the live queries of a short batch share all W of them, in the
    envelope, on the wire and in ``stats.lanes``."""
    from repro.serving.protocol import envelope_to_wire

    h = GraphHandle(g=small_powerlaw["g"], eg=small_powerlaw["eg"])
    sess = SimRankSession(h, c=0.6, eps_a=0.2, top_k=5, batch_q=4, seed=0,
                          walk_chunk=64)
    nodes = np.argsort(-np.asarray(h.g.in_deg))[:6].astype(int)
    for u in nodes:
        sess.submit(int(u))
    res = sess.drain(budget_walks=64)
    assert [r.lanes for r in res] == [16] * 4 + [32] * 2
    assert sess.stats.lanes == 32
    assert envelope_to_wire(res[5])["lanes"] == 32
    assert res[4].probe_levels < res[0].probe_levels  # two walk sets, not 4
    sess.submit(int(nodes[0]))
    (lone,) = sess.drain(budget_walks=64)
    assert lone.lanes == sess.stats.lanes == 64
    assert lone.push_path == "coo_xla"


def test_short_batch_reports_its_walks_sampled(small_powerlaw):
    """A drained dispatch's pool holds n_r walks for each live query and
    none for the padding: its answers, the wire and
    ``stats.walks_sampled`` report live * n_r."""
    from repro.serving.protocol import envelope_to_wire

    h = GraphHandle(g=small_powerlaw["g"], eg=small_powerlaw["eg"])
    sess = SimRankSession(h, c=0.6, eps_a=0.2, top_k=5, batch_q=4, seed=0,
                          walk_chunk=64)
    assert sess.stats.walks_sampled is None
    nodes = np.argsort(-np.asarray(h.g.in_deg))[:6].astype(int)
    for u in nodes:
        sess.submit(int(u))
    res = sess.drain(budget_walks=48)
    assert [r.walks_sampled for r in res] == [4 * 48] * 4 + [2 * 48] * 2
    assert sess.stats.walks_sampled == 2 * 48
    assert envelope_to_wire(res[5])["walks_sampled"] == 2 * 48
    assert sess.backend.walks_sampled == 2 * 48
    sess.submit(int(nodes[0]))
    (lone,) = sess.drain(budget_walks=48)
    assert lone.walks_sampled == sess.stats.walks_sampled == 48
    assert sess.stats.as_dict()["walks_sampled"] == 48


def test_drain_cuts_batches_at_group_change(small_powerlaw):
    """Specs with different (kind, k, budget) never share a dispatch."""
    h = GraphHandle(g=small_powerlaw["g"], eg=small_powerlaw["eg"])
    sess = SimRankSession(h, c=0.6, eps_a=0.2, top_k=5, batch_q=4, seed=0,
                          walk_chunk=64)
    u = int(np.argmax(np.asarray(h.g.in_deg)))
    sess.submit(QuerySpec(kind="topk", node=u, k=5, budget_walks=64))
    sess.submit(QuerySpec(kind="topk", node=u, k=3, budget_walks=64))
    sess.submit(QuerySpec(kind="single_source", node=u, budget_walks=64))
    res = sess.drain()
    assert sess.stats.steps == 3  # one dispatch per group
    assert res[0].topk_nodes.shape == (5,)
    assert res[1].topk_nodes.shape == (3,)
    assert res[2].scores.shape == (h.n,)


# ---------------------------------------------------------------------------
# Planner (§4.4 promoted host-side) + error bound at the effective budget
# ---------------------------------------------------------------------------


def test_planner_resolves_auto_to_legacy_variant(toy_session, key):
    sess = toy_session
    # toy node 0 has tiny in-degree and the full n_r is large -> tree
    spec = QuerySpec(kind="single_source", node=0, variant="auto", key=key)
    assert sess.plan(spec) == "tree"
    # a capped budget comparable to the in-degree -> fused telescoped
    d = int(sess.handle.eg.in_deg[0])
    capped = QuerySpec(kind="single_source", node=0, variant="auto",
                       budget_walks=max(1, 2 * d), key=key)
    assert sess.plan(capped) == "telescoped"
    # batched specs always take the fused path
    assert sess.plan(QuerySpec(kind="topk", nodes=(0, 2), k=2)) == "telescoped"
    # auto == the explicit variant it planned, bit-for-bit
    env_auto = sess.query(spec)
    env_tree = sess.query(
        QuerySpec(kind="single_source", node=0, variant="tree", key=key)
    )
    assert env_auto.variant == "tree"
    assert np.array_equal(env_auto.scores, env_tree.scores)


def test_error_bound_at_effective_budget(toy_session, key):
    sess = toy_session
    full = sess.query(QuerySpec(kind="topk", node=0, key=key))
    capped = sess.query(QuerySpec(kind="topk", node=0, key=key, budget_walks=32))
    assert capped.walks_used == 32 and full.walks_used == sess.params.n_r
    # anytime queries report the looser bound they actually guarantee
    assert capped.error_bound > full.error_bound
    assert full.error_bound <= sess.params.eps_a + 1e-9
    assert capped.error_bound == pytest.approx(
        abs_error_bound(sess.params, n=sess.handle.n, n_r=32)
    )


# ---------------------------------------------------------------------------
# Updates through the session surface: invariants re-asserted
# ---------------------------------------------------------------------------


@pytest.fixture()
def er_session():
    src, dst, n = erdos_renyi_graph(60, 300, seed=5)
    h = GraphHandle.from_edges(
        src, dst, n,
        capacity=len(src) + 64,
        k_max=int(np.bincount(dst, minlength=n).max()) + 8,
    )
    return src, dst, SimRankSession(
        h, c=0.3, eps_a=0.3, top_k=2, batch_q=2, update_batch=8, seed=0
    )


def test_update_mirror_equals_rebuild(er_session):
    src, dst, sess = er_session
    rng = np.random.default_rng(1)
    rep = sess.update(inserts=(rng.integers(0, 60, 10), rng.integers(0, 60, 10)))
    assert rep.applied == 10 and rep.version == 1
    rep2 = sess.update(deletes=(src[:5], dst[:5]))
    assert rep2.applied == 5 and rep2.version == 2
    _mirrors_equal_rebuild(sess.handle)
    assert sess.stats.updates == 15


def test_update_multigraph_duplicate_deletes_vectorized(er_session):
    """Duplicate (s, d) pairs in ONE delete call remove one copy per op —
    the np.unique/cumsum occurrence split preserves the one-copy-per-batch
    semantics of the seed's python loop."""
    src, dst, sess = er_session
    base = sess.handle.num_edges
    fresh_s, fresh_d = (int(src[0]) + 9) % 60, int(dst[0])
    sess.update(inserts=([fresh_s] * 3, [fresh_d] * 3))
    assert sess.handle.num_edges == base + 3
    rep = sess.update(deletes=([fresh_s] * 3, [fresh_d] * 3))
    assert rep.applied == 3
    assert sess.handle.num_edges == base
    _mirrors_equal_rebuild(sess.handle)


def test_occurrence_numbers_match_seed_loop():
    from repro.api.session import _occurrence_numbers

    rng = np.random.default_rng(0)
    s = rng.integers(0, 4, 40).astype(np.int32)
    d = rng.integers(0, 4, 40).astype(np.int32)
    seen, occ_ref = {}, np.empty(40, np.int64)
    for i, (a, b) in enumerate(zip(s.tolist(), d.tolist())):  # the seed loop
        occ_ref[i] = seen.get((a, b), 0)
        seen[(a, b)] = occ_ref[i] + 1
    np.testing.assert_array_equal(_occurrence_numbers(s, d, 4), occ_ref)


def test_update_overflow_sticky_and_regrow_via_session():
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    h = GraphHandle.from_edges(src, dst, 6, capacity=4, k_max=2)
    sess = SimRankSession(h, c=0.3, eps_a=0.3, top_k=2, seed=0,
                          auto_regrow=False)
    rep = sess.update(inserts=([3, 4, 5], [0, 1, 2]))
    assert rep.applied == 1 and rep.overflow
    assert sorted(rep.skipped) == [(4, 1, True), (5, 2, True)]
    assert sess.overflow and sess.version == 1  # sticky + one bump
    _mirrors_equal_rebuild(sess.handle)  # the skip hit BOTH mirrors
    v = sess.version
    sess.regrow()
    assert not sess.overflow and sess.version == v  # representation change
    rep2 = sess.update(inserts=([4, 5], [1, 2]))
    assert rep2.applied == 2 and not sess.overflow
    assert sess.handle.num_edges == 6
    _mirrors_equal_rebuild(sess.handle)


def test_update_auto_regrow_retries_until_applied():
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    h = GraphHandle.from_edges(src, dst, 6, capacity=4, k_max=2)
    sess = SimRankSession(h, c=0.3, eps_a=0.3, top_k=2, seed=0)
    rep = sess.update(inserts=([3, 4, 5], [0, 1, 2]))
    assert rep.applied == 3 and rep.regrows >= 1 and not rep.skipped
    assert not sess.overflow and sess.handle.num_edges == 6
    _mirrors_equal_rebuild(sess.handle)


def test_update_rejects_out_of_range_ops(er_session):
    _, _, sess = er_session
    with pytest.raises(ValueError):
        sess.update(inserts=([60], [0]))
    with pytest.raises(ValueError):
        sess.queue_update([0], [-1])
    assert sess.pending == (0, 0)


# ---------------------------------------------------------------------------
# Fused epochs through the session surface
# ---------------------------------------------------------------------------


def test_epoch_scores_equal_rebuild_via_session(er_session):
    """Epoch scores on the incrementally-updated graph == multi_source on a
    from-scratch rebuild under the session's submit-time streams."""
    src, dst, sess = er_session
    n = 60
    rng = np.random.default_rng(3)
    new_s = rng.integers(0, n, 8).astype(np.int32)
    new_d = rng.integers(0, n, 8).astype(np.int32)
    queries = [1, 2]
    ep = sess.epoch(inserts=(new_s, new_d), queries=queries, budget_walks=64)
    assert ep.version == 1 and len(ep.results) == 2
    _mirrors_equal_rebuild(sess.handle)

    src2 = np.concatenate([src, new_s])
    dst2 = np.concatenate([dst, new_d])
    h_rb = GraphHandle.from_edges(src2, dst2, n, capacity=sess.handle.capacity,
                                  k_max=sess.handle.k_max)
    keys = jnp.stack(
        [jax.random.fold_in(jax.random.key(0), i) for i in range(2)]
    )
    est = np.asarray(multi_source(
        None, h_rb.g, h_rb.eg, jnp.asarray(queries, jnp.int32),
        make_params(n, c=0.3, eps_a=0.3, delta=0.01),
        lanes=256, n_r=64, keys=keys,
    ))
    for i, res in enumerate(ep.results):
        expect = est[i].copy()
        expect[queries[i]] = -np.inf  # top-k excludes the query node
        order = np.argsort(-expect, kind="stable")[:2]
        np.testing.assert_allclose(res.topk_scores, expect[order], atol=1e-5)
        assert res.version == 1 and res.walks_used == 64


def test_epoch_single_source_kind_returns_score_vectors(er_session):
    """A single_source query batch rides the SAME fused epoch (top_k=0) and
    returns full estimate vectors — queries and updates, one surface."""
    src, dst, sess = er_session
    n = 60
    specs = [QuerySpec(kind="single_source", node=u) for u in (1, 2)]
    ep = sess.epoch(inserts=(src[:1] * 0 + 7, dst[:1] * 0 + 3),
                    queries=specs, budget_walks=64)
    assert len(ep.results) == 2
    src2 = np.concatenate([src, [7]])
    dst2 = np.concatenate([dst, [3]])
    h_rb = GraphHandle.from_edges(src2, dst2, n, capacity=sess.handle.capacity,
                                  k_max=sess.handle.k_max)
    keys = jnp.stack(
        [jax.random.fold_in(jax.random.key(0), i) for i in range(2)]
    )
    est = np.asarray(multi_source(
        None, h_rb.g, h_rb.eg, jnp.asarray([1, 2], jnp.int32),
        make_params(n, c=0.3, eps_a=0.3, delta=0.01),
        lanes=256, n_r=64, keys=keys,
    ))
    for i, res in enumerate(ep.results):
        assert res.kind == "single_source" and res.scores.shape == (n,)
        np.testing.assert_allclose(res.scores, est[i], atol=1e-5)


# ---------------------------------------------------------------------------
# Deprecation shims: legacy entry points delegate and warn
# ---------------------------------------------------------------------------


def test_single_source_simple_shim_regression(toy, key):
    """The legacy bare-EllGraph form == single_source(key, eg, eg, ...)
    EXACTLY (the silent both-mirrors choice, now explicit + warned), and
    the GraphHandle form uses the proper (COO push, ELL gather) pair."""
    eg, n = toy["eg"], toy["n"]
    params = make_params(n, c=0.25, eps_a=0.1, delta=0.01)
    with pytest.warns(DeprecationWarning):
        est = single_source_simple(key, eg, 0, c=0.25, eps_a=0.1, delta=0.01)
    ref = single_source(key, eg, eg, 0, params)
    assert np.array_equal(np.asarray(est), np.asarray(ref))

    h = GraphHandle(g=toy["g"], eg=eg)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # handle form must NOT warn
        est_h = single_source_simple(key, h, 0, c=0.25, eps_a=0.1, delta=0.01)
    ref_h = single_source(key, toy["g"], eg, 0, params)
    assert np.array_equal(np.asarray(est_h), np.asarray(ref_h))


def test_engine_shims_warn_and_delegate(small_powerlaw):
    from repro.serving import DynamicEngine, SimRankEngine

    g, eg = small_powerlaw["g"], small_powerlaw["eg"]
    with pytest.warns(DeprecationWarning):
        eng = SimRankEngine(g, eg, eps_a=0.2, top_k=3, batch_q=2, seed=7,
                            walk_chunk=64)
    u = int(np.argmax(np.asarray(g.in_deg)))
    res = eng.run_query(u, budget_walks=64)
    assert isinstance(res, ResultEnvelope)
    # shim result == the session serving the same spec with the same stream
    h = GraphHandle(g=g, eg=eg)
    sess = SimRankSession(h, c=0.6, eps_a=0.2, top_k=3, batch_q=2, seed=7,
                          walk_chunk=64)
    spec = QuerySpec(kind="topk", node=u, k=3, variant="telescoped")
    ref = sess._serve_fused([(spec, sess._query_key())], 64)[0]
    assert np.array_equal(res.topk_scores, ref.topk_scores)
    assert eng.stats.queries == 1 and eng.session is not None

    with pytest.warns(DeprecationWarning):
        deng = DynamicEngine(g, eg, eps_a=0.2, top_k=3, batch_q=2,
                             update_batch=4, seed=0)
    held = deng.stats  # legacy contract: ONE live object, not a snapshot
    deng.submit(u)
    ep = deng.step(budget_walks=64)
    assert ep.results[0].version == 0
    assert deng.stats.epochs == 1 and deng.pending == (0, 0)
    assert held.epochs == 1  # the held reference stayed current


def test_engine_mirror_setters_copy_and_validate(small_powerlaw):
    """Assigning eng.g/eng.eg own-copies (donated epoch steps must never
    share caller buffers) and rejects a mismatched mirror."""
    from repro.serving import DynamicEngine

    g, eg, n = small_powerlaw["g"], small_powerlaw["eg"], small_powerlaw["n"]
    with pytest.warns(DeprecationWarning):
        eng = DynamicEngine(g, eg, eps_a=0.2, top_k=2, batch_q=2,
                            update_batch=4, seed=0)
    mine = graph_from_edges(small_powerlaw["src"], small_powerlaw["dst"], n,
                            capacity=int(g.capacity))
    before = np.asarray(mine.src).copy()
    eng.g = mine  # must copy, not alias
    eng.insert([1], [2])
    eng.submit(1)
    eng.step(budget_walks=16)  # donates the engine's buffers ...
    np.testing.assert_array_equal(np.asarray(mine.src), before)  # ... not mine
    with pytest.raises(ValueError):
        eng.eg = ell_from_edges(small_powerlaw["src"], small_powerlaw["dst"],
                                n + 1)


def test_abs_error_bound_rejects_nonpositive_budget(toy):
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01)
    with pytest.raises(ValueError):
        abs_error_bound(params, n=toy["n"], n_r=0)


def test_unowned_session_refuses_epoch(toy_handle):
    """own_graph=False shares the caller's buffers — the donating epoch
    step must refuse rather than invalidate them."""
    sess = SimRankSession(toy_handle, c=0.25, eps_a=0.1, top_k=2,
                          own_graph=False)
    with pytest.raises(ValueError, match="own_graph"):
        sess.epoch(queries=[0], budget_walks=16)
    # queries and immediate updates remain available on a shared handle
    assert sess.query(QuerySpec(kind="topk", node=0, budget_walks=32)).node == 0


def test_legacy_queryresult_positional_construction():
    from repro.serving import QueryResult

    res = QueryResult(3, np.array([1, 2]), np.array([0.5, 0.4]), 64, 0.1)
    assert isinstance(res, ResultEnvelope)
    assert res.node == 3 and res.walks_used == 64  # old field order binds
    assert list(res.topk_nodes) == [1, 2] and res.version == -1


def test_session_requires_handle(toy):
    with pytest.raises(TypeError):
        SimRankSession(toy["g"])
    with pytest.raises(ValueError):
        QuerySpec(kind="topk")  # neither node nor nodes
    with pytest.raises(ValueError):
        QuerySpec(kind="nope", node=0)


def test_session_owns_graph_state(er_session):
    """The session own-copies its handle: the caller's handle (and the
    arrays under it) are untouched by donated epoch steps."""
    src, dst, _ = er_session
    n = 60
    h = GraphHandle.from_edges(src, dst, n, capacity=len(src) + 64,
                               k_max=int(np.bincount(dst, minlength=n).max()) + 8)
    before = np.asarray(h.g.src).copy()
    sess = SimRankSession(h, c=0.3, eps_a=0.3, top_k=2, batch_q=2,
                          update_batch=4, seed=0)
    sess.epoch(inserts=([1], [2]), queries=[1], budget_walks=16)
    np.testing.assert_array_equal(np.asarray(h.g.src), before)
    assert h.version == 0 and sess.version == 1


def test_session_stats_threading(er_session):
    src, dst, sess = er_session
    sess.query(QuerySpec(kind="topk", node=1, budget_walks=32))
    sess.submit(1)
    sess.submit(2)
    sess.drain(budget_walks=32)
    sess.update(inserts=([1], [2]))
    sess.epoch(queries=[3], budget_walks=32)
    s = sess.stats
    assert s.queries == 4  # 1 query() + 2 drained + 1 epoch
    assert s.steps == 3  # query() + 1 drain batch + 1 epoch dispatch
    assert s.updates == 1 and s.epochs == 1
    assert s.as_dict()["queries"] == 4


def test_concurrent_submit_drain_thread_safe():
    """Many threads submitting (+ some draining) concurrently: every
    ticket gets exactly its own answer, bitwise-equal to a replay of its
    slot in the dispatch that served it, and equal to a solo replay up to
    the order of f32 sums, its nodes up to ties (a short batch deals its
    lane columns to its live queries, so how many a query owns depends
    on the batch).

    This is the contract the serving collector (serving/service.py)
    builds on: handler threads call ``submit()`` while the collector
    drains, and the lock around queue mutation + ticket fill must keep
    (spec, key, ticket) triples intact under interleaving.
    """
    import threading

    src, dst, n = erdos_renyi_graph(60, 300, seed=5)
    h = GraphHandle.from_edges(src, dst, n)
    sess = SimRankSession(h, c=0.3, eps_a=0.3, top_k=3, batch_q=4, seed=0)
    sess.query(QuerySpec(kind="topk", node=0, budget_walks=16))  # warm jit
    dispatches = []  # (us, keys, kwargs) of every fused dispatch
    serve = sess.backend.serve_batch

    def recorded(kind, us, keys, **kw):
        dispatches.append((list(us), keys, kw))
        return serve(kind, us, keys, **kw)

    sess.backend.serve_batch = recorded

    T, PER = 8, 6
    tickets = [[None] * PER for _ in range(T)]
    barrier = threading.Barrier(T)

    def worker(t):
        barrier.wait()
        for j in range(PER):
            q = (t * PER + j) % n
            tickets[t][j] = sess.submit(QuerySpec(
                kind="topk", node=q, k=3, budget_walks=16,
                key=jax.random.key(10_000 + t * PER + j),
            ))
            if j % 3 == 2:
                sess.drain(budget_walks=16)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    sess.drain(budget_walks=16)

    assert sess.stats.queries == T * PER + 1  # + the warm-jit query()
    assert not sess.query_queue
    ref = SimRankSession(h, c=0.3, eps_a=0.3, top_k=3, batch_q=4, seed=99)
    # each ticket's (key, slot) in the dispatch that served it
    slot_of = {}
    key_id = lambda data: tuple(np.asarray(data).tolist())  # noqa: E731
    for d, (_, keys, kw) in enumerate(dispatches):
        data = jax.random.key_data(keys)
        for i in range(kw["live"]):
            slot_of[key_id(data[i])] = (d, i)
    replays = {}
    for t in range(T):
        for j in range(PER):
            tk = tickets[t][j]
            assert tk is not None and tk.envelope is not None
            q = (t * PER + j) % n
            assert tk.envelope.node == q
            key = jax.random.key(10_000 + t * PER + j)
            d, i = slot_of[key_id(jax.random.key_data(key))]
            if d not in replays:
                us, keys, kw = dispatches[d]
                replays[d] = ref.backend.serve_batch("topk", us, keys, **kw)
            _, idx, vals, _ = replays[d]
            assert dispatches[d][0][i] == q
            np.testing.assert_array_equal(
                np.asarray(tk.envelope.topk_nodes), idx[i]
            )
            np.testing.assert_array_equal(
                np.asarray(tk.envelope.topk_scores), vals[i]
            )
            rtk = ref.submit(QuerySpec(
                kind="topk", node=q, k=3, budget_walks=16, key=key,
            ))
            ref.drain()
            np.testing.assert_allclose(
                np.asarray(tk.envelope.topk_scores),
                np.asarray(rtk.envelope.topk_scores), atol=1e-5,
            )
            # the nodes up to ties: the solo estimate at each returned
            # node is the score returned for it
            ss = ref.submit(QuerySpec(
                kind="single_source", node=q, budget_walks=16, key=key,
            ))
            ref.drain()
            np.testing.assert_allclose(
                np.asarray(ss.envelope.scores)[tk.envelope.topk_nodes],
                np.asarray(tk.envelope.topk_scores), atol=1e-5,
            )
