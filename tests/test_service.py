"""Serving subsystem (serving/{protocol,service,server}): wire validation,
micro-batching parity, admission control, deadlines, tenancy, updates.

The load-bearing test is ``test_microbatch_parity_and_fusion``: N client
threads x 1 query each through the live HTTP server must be bitwise-equal
to a direct session under matched streams (wire ``seed`` pins the lane
PRNG stream; the reference replays each dispatch the collector cut, and
each (node, key) alone through ``submit()``/``drain()`` to f32 summation
order: how many lane columns a query owns depends on how many queries
its batch held) — AND the tenant session must report ``steps <
queries`` (the window really fused cross-connection traffic into
lane-batched dispatches).
"""
import threading
import time

import numpy as np
import pytest

import jax

from repro.api.handle import GraphHandle
from repro.api.session import SimRankSession
from repro.api.spec import QuerySpec
from repro.serving import (
    AdmissionError,
    ProtocolError,
    ServiceClient,
    ServiceConfig,
    SimRankService,
    parse_query_request,
    parse_update_request,
    start_server,
    stop_server,
)
from repro.serving.protocol import QueryRequest


@pytest.fixture(scope="module")
def service_graph():
    rng = np.random.default_rng(7)
    n = 48
    src = rng.integers(0, n, 300)
    dst = rng.integers(0, n, 300)
    return GraphHandle.from_edges(src, dst, n), n


def _live_server(handle, **cfg_kw):
    cfg_kw.setdefault("batch_window_ms", 40.0)
    cfg_kw.setdefault("max_batch_q", 8)
    cfg_kw.setdefault("default_budget_walks", 64)
    session_kwargs = cfg_kw.pop("session_kwargs", None)
    backend = cfg_kw.pop("backend", "local")
    svc = SimRankService(
        handle, backend=backend, config=ServiceConfig(**cfg_kw),
        session_kwargs=session_kwargs,
    )
    server, thread = start_server(svc)
    return svc, server, thread


# -- protocol ----------------------------------------------------------------


def test_parse_query_request_validates():
    req = parse_query_request(
        {"node": 3, "kind": "single_source", "budget_walks": 32, "seed": 9}
    )
    assert req == QueryRequest(
        kind="single_source", node=3, budget_walks=32, seed=9
    )
    with pytest.raises(ProtocolError, match="unknown query field"):
        parse_query_request({"node": 1, "budget_walk": 8})  # the typo trap
    with pytest.raises(ProtocolError, match="requires a 'node'"):
        parse_query_request({"kind": "topk"})
    with pytest.raises(ProtocolError, match="kind"):
        parse_query_request({"node": 1, "kind": "pagerank"})
    with pytest.raises(ProtocolError, match="integer"):
        parse_query_request({"node": 1.5})
    with pytest.raises(ProtocolError, match=">= 1"):
        parse_query_request({"node": 1, "k": 0})
    with pytest.raises(ProtocolError, match="confidence requires epsilon"):
        parse_query_request({"node": 1, "confidence": 0.95})
    with pytest.raises(ProtocolError, match="finite"):
        parse_query_request({"node": 1, "epsilon": float("nan")})


def test_parse_update_request_validates():
    ins, dels = parse_update_request({"inserts": [[1, 2], [3, 4]]})
    assert ins.shape == (2, 2) and dels is None
    assert ins.tolist() == [[1, 2], [3, 4]]
    with pytest.raises(ProtocolError, match="no ops"):
        parse_update_request({"inserts": []})
    with pytest.raises(ProtocolError, match="pair"):
        parse_update_request({"inserts": [[1, 2, 3]]})
    with pytest.raises(ProtocolError, match="negative"):
        parse_update_request({"deletes": [[-1, 2]]})
    with pytest.raises(ProtocolError, match="unknown update field"):
        parse_update_request({"insert": [[1, 2]]})


# -- the tentpole: micro-batch window, bitwise parity ------------------------


def test_microbatch_parity_and_fusion(service_graph):
    """N threads x 1 query via HTTP == direct session, and steps < queries."""
    handle, n = service_graph
    svc, server, thread = _live_server(handle, batch_window_ms=60.0)
    host, port = server.server_address
    dispatches = []  # (us, keys, kwargs) of every fused dispatch
    backend = svc.session().backend
    serve = backend.serve_batch

    def recorded(kind, us, keys, **kw):
        dispatches.append((list(us), keys, kw))
        return serve(kind, us, keys, **kw)

    backend.serve_batch = recorded
    try:
        Q = 16
        results: list[dict | None] = [None] * Q
        barrier = threading.Barrier(Q)

        def go(i):
            with ServiceClient(host, port) as cl:
                barrier.wait()  # land inside one collector window
                results[i] = cl.query(
                    node=i, kind="topk", k=5, budget_walks=64, seed=500 + i
                )

        threads = [
            threading.Thread(target=go, args=(i,)) for i in range(Q)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert all(r is not None for r in results)

        # direct-session reference: same (node, key) streams, same
        # batch_q geometry; replaying the dispatch the collector cut is
        # bitwise what the query's lanes computed, a solo replay is that
        # to f32 summation order (the live queries share all lanes)
        ref = SimRankSession(handle, batch_q=svc.config.max_batch_q)
        slot_of, replays = {}, {}
        key_id = lambda data: tuple(np.asarray(data).tolist())  # noqa: E731
        for d, (_, keys, kw) in enumerate(dispatches):
            data = jax.random.key_data(keys)
            for j in range(kw["live"]):
                slot_of[key_id(data[j])] = (d, j)
        for i, r in enumerate(results):
            key = jax.random.key(500 + i)
            d, j = slot_of[key_id(jax.random.key_data(key))]
            if d not in replays:
                us, keys, kw = dispatches[d]
                out = ref.backend.serve_batch("topk", us, keys, **kw)
                replays[d] = (out, ref.backend.lanes)
            (_, idx, vals, _), lanes = replays[d]
            assert r["topk_nodes"] == np.asarray(idx[j]).tolist()
            # JSON carries exact float64 widenings of the float32 scores:
            # the cast back must be bit-identical
            assert np.array_equal(
                np.asarray(r["topk_scores"], np.float32), vals[j]
            )
            assert r["lanes"] == lanes[j]
            assert r["walks_sampled"] == kw["live"] * 64
            tk = ref.submit(QuerySpec(
                kind="topk", node=i, k=5, budget_walks=64, key=key,
            ))
            ref.drain()
            env = tk.envelope
            np.testing.assert_allclose(
                np.asarray(r["topk_scores"], np.float32),
                np.asarray(env.topk_scores), atol=1e-5,
            )
            # the nodes up to ties: the solo estimate at each returned
            # node is the score returned for it
            ss = ref.submit(QuerySpec(
                kind="single_source", node=i, budget_walks=64, key=key,
            ))
            ref.drain()
            np.testing.assert_allclose(
                np.asarray(ss.envelope.scores)[r["topk_nodes"]],
                np.asarray(r["topk_scores"], np.float32), atol=1e-5,
            )
            assert r["version"] == env.version
            assert r["walks_used"] == env.walks_used

        # the whole point of the window: fused dispatches, not per-query
        sess_stats = svc.stats_snapshot()["tenants"]["default"]
        assert sess_stats["queries"] == Q
        assert sess_stats["steps"] < Q
        assert sum(svc.stats.batch_hist.values()) == svc.stats.batches
        assert max(svc.stats.batch_hist) > 1  # a real multi-query cut
        assert svc.stats.errors_5xx == 0
    finally:
        stop_server(server, thread)


def test_single_source_roundtrip(service_graph):
    handle, n = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    try:
        with ServiceClient(host, port) as cl:
            r = cl.query(
                node=2, kind="single_source", budget_walks=32, seed=11
            )
        assert len(r["scores"]) == n
        ref = SimRankSession(handle, batch_q=svc.config.max_batch_q)
        tk = ref.submit(QuerySpec(
            kind="single_source", node=2, budget_walks=32,
            key=jax.random.key(11),
        ))
        ref.drain()
        assert np.array_equal(
            np.asarray(r["scores"], np.float32), np.asarray(tk.envelope.scores)
        )
    finally:
        stop_server(server, thread)


def test_walks_sampled_reaches_answer_and_stats(service_graph):
    """A lone query's dispatch samples its own n_r walks, not batch_q
    times that: the answer and the tenant's ``/stats`` both say so."""
    handle, n = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    try:
        with ServiceClient(host, port) as cl:
            r = cl.query(node=3, kind="topk", k=5, budget_walks=40, seed=3)
            stats = cl.stats()
        assert r["batch_size"] == 1
        assert r["walks_sampled"] == 40
        assert stats["tenants"]["default"]["walks_sampled"] == 40
    finally:
        stop_server(server, thread)


# -- admission control / deadlines -------------------------------------------


def test_admission_control_429(service_graph):
    handle, _ = service_graph
    svc, server, thread = _live_server(
        handle, max_inflight=2, batch_window_ms=250.0, max_batch_q=64
    )
    host, port = server.server_address
    try:
        req = QueryRequest(node=1, budget_walks=16)
        svc.enqueue(req)
        svc.enqueue(req)
        with pytest.raises(AdmissionError) as ei:
            svc.enqueue(req)
        assert ei.value.retry_after_s > 0
        # and over the wire: 429 + Retry-After + machine-readable hint
        with ServiceClient(host, port) as cl:
            status, payload = cl.query_raw(node=1, budget_walks=16)
        assert status == 429
        assert payload["retry_after_s"] > 0
        assert svc.stats.rejected_429 == 2
    finally:
        stop_server(server, thread)


def test_flat_deadline_sheds_504(service_graph):
    handle, _ = service_graph
    # window far longer than the deadline: the request must expire queued
    svc, server, thread = _live_server(handle, batch_window_ms=300.0)
    host, port = server.server_address
    try:
        with ServiceClient(host, port) as cl:
            status, payload = cl.query_raw(
                node=1, budget_walks=16, deadline_s=0.01
            )
        assert status == 504
        assert "deadline" in payload["error"]
        assert svc.stats.shed_504 == 1
        assert svc.stats.errors_5xx == 0
    finally:
        stop_server(server, thread)


def test_adaptive_deadline_degrades_not_sheds(service_graph):
    """epsilon + deadline -> best-so-far certificate, not a 504."""
    handle, _ = service_graph
    svc, server, thread = _live_server(handle, batch_window_ms=1.0)
    host, port = server.server_address
    try:
        with ServiceClient(host, port) as cl:
            r = cl.query(
                node=3, epsilon=1e-6, confidence=0.99,
                budget_walks=128, deadline_s=30.0,
            )
        # an unreachable epsilon stops on budget or deadline — either way
        # the response is a 200 with an honest certificate
        assert r["certificate"] in ("budget", "deadline")
        assert r["certified_bound"] > 0
        assert r["batch_size"] == 1  # adaptive+deadline dispatches solo
    finally:
        stop_server(server, thread)


# -- tenancy / updates -------------------------------------------------------


def test_tenants_isolated_stats_shared_graph(service_graph):
    handle, _ = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    try:
        with ServiceClient(host, port, tenant="alice") as ca, \
                ServiceClient(host, port, tenant="bob") as cb:
            ra = ca.query(node=1, budget_walks=16)
            rb = cb.query(node=2, budget_walks=16)
            assert ra["tenant"] == "alice" and rb["tenant"] == "bob"
            v0 = ra["version"]
            # an update through EITHER tenant bumps the version BOTH see
            rep = ca.update(inserts=[(5, 6)])
            assert rep["version"] == v0 + 1
            ra2 = ca.query(node=1, budget_walks=16)
            rb2 = cb.query(node=2, budget_walks=16)
            assert ra2["version"] == rb2["version"] == v0 + 1
            stats = ca.stats()
        assert set(stats["tenants"]) >= {"alice", "bob"}
        assert stats["tenants"]["alice"]["queries"] == 2
        assert stats["tenants"]["bob"]["queries"] == 2
        # distinct PRNG namespaces: session keys differ per tenant
        assert svc.session("alice") is not svc.session("bob")
        # ... over ONE shared graph object
        assert svc.session("alice").handle is svc.session("bob").handle
        with pytest.raises(ProtocolError, match="tenant"):
            svc.session("no spaces allowed")
    finally:
        stop_server(server, thread)


def test_update_validation_and_health(service_graph):
    handle, _ = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    try:
        with ServiceClient(host, port) as cl:
            h = cl.healthz()
            assert h["status"] == "ok" and h["n"] == handle.n
            with pytest.raises(RuntimeError, match="400"):
                cl.update(inserts=[])  # no ops
            # node out of range -> 400, not a jax crash
            status, payload = cl.query_raw(node=10**6, budget_walks=16)
            assert status == 400
            assert "out of range" in payload["error"]
    finally:
        stop_server(server, thread)


def test_service_close_rejects_503(service_graph):
    handle, _ = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    stop_server(server, thread)
    from repro.serving import ServiceClosed

    with pytest.raises(ServiceClosed):
        svc.enqueue(QueryRequest(node=1, budget_walks=16))


def test_collector_survives_group_failure(service_graph):
    """A dispatch error 500s its own batch and leaves the service live."""
    handle, _ = service_graph
    svc, server, thread = _live_server(handle)
    host, port = server.server_address
    try:
        # sabotage one tenant's session so its group throws at dispatch
        bad = svc.session("mallory")
        bad.backend = None  # AttributeError inside the collector
        with ServiceClient(host, port, tenant="mallory") as cm:
            status, payload = cm.query_raw(node=1, budget_walks=16)
        assert status == 500
        assert svc.stats.errors_5xx == 1
        # the collector thread is still alive and serving other tenants
        with ServiceClient(host, port) as cl:
            r = cl.query(node=1, budget_walks=16)
        assert r["kind"] == "topk"
    finally:
        stop_server(server, thread)


# -- per-tenant admission quotas / priority lane ------------------------------


def test_tenant_quota_greedy_vs_quiet(service_graph):
    """A greedy tenant 429s at its own share while a quiet tenant's
    queries still admit (the global bound alone would starve everyone)."""
    handle, _ = service_graph
    svc = SimRankService(handle, config=ServiceConfig(
        max_inflight=64, tenant_max_inflight=2,
        batch_window_ms=250.0, max_batch_q=64, default_budget_walks=16,
    ))
    try:
        req = QueryRequest(node=1, budget_walks=16)
        greedy = [svc.enqueue(req, "greedy"), svc.enqueue(req, "greedy")]
        with pytest.raises(AdmissionError) as ei:
            svc.enqueue(req, "greedy")  # over its share, global slots free
        assert ei.value.retry_after_s > 0
        assert svc.stats.rejected_429 == 1
        quiet = svc.enqueue(req, "quiet")  # unaffected by greedy's 429
        for item in greedy + [quiet]:
            assert item.event.wait(timeout=30.0)
            assert item.status == 200
        # quota slots freed with the responses: greedy admits again
        svc.enqueue(req, "greedy").event.wait(timeout=30.0)
        snap = svc.stats_snapshot()["service"]
        assert snap["tenant_max_inflight"] == 2
        assert snap["tenant_inflight"] == {}  # all drained
    finally:
        svc.close()


def test_cut_window_priority_lane(service_graph):
    """When pending overflows one cut, deadline-bearing queries take the
    lane slots (earliest deadline first); deadline-free keep FIFO order
    behind them, and the remainder keeps arrival order."""
    handle, _ = service_graph
    svc = SimRankService(handle, config=ServiceConfig(
        max_batch_q=2, batch_window_ms=0.0,
    ))
    svc.close()  # stop the collector; drive _cut_window by hand
    from repro.serving.service import _PendingQuery

    def pend(name, t_enq, t_deadline):
        it = _PendingQuery(None, None, "t", t_enq, t_deadline)
        it.payload = {"name": name}
        return it

    # arrival order: two deadline-free first, then two with deadlines
    svc._pending.extend([
        pend("free-a", 1.0, None),
        pend("free-b", 2.0, None),
        pend("dl-late", 3.0, 50.0),
        pend("dl-soon", 4.0, 10.0),
    ])
    cut = svc._cut_window()
    assert [it.payload["name"] for it in cut] == ["dl-soon", "dl-late"]
    assert [it.payload["name"] for it in svc._pending] == ["free-a", "free-b"]
    # under one full cut the window stays plain FIFO
    cut = svc._cut_window()
    assert [it.payload["name"] for it in cut] == ["free-a", "free-b"]
    assert not svc._pending


def test_tenant_quota_validation():
    with pytest.raises(ValueError, match="tenant_max_inflight"):
        ServiceConfig(tenant_max_inflight=0)
