"""Backend layer: LocalBackend extraction parity, QueryTicket serving,
ShardedBackend semantics (single-shard in-process; the 8-fake-device mesh
parity + sharded-update invariant run in a subprocess, like
test_distributed, because XLA_FLAGS must precede jax init)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    Backend,
    GraphHandle,
    LocalBackend,
    QuerySpec,
    ShardedBackend,
    ShardedGraphState,
    SimRankSession,
)
from repro.core import make_params
from repro.core.probesim import single_source, topk


@pytest.fixture()
def handle(small_powerlaw):
    d = small_powerlaw
    in_deg = np.bincount(d["dst"], minlength=d["n"])
    return GraphHandle.from_edges(
        d["src"], d["dst"], d["n"],
        capacity=len(d["src"]) + 64, k_max=int(in_deg.max()) + 8,
    )


# ---------------------------------------------------------------------------
# LocalBackend: the extraction must be bit-identical to the core calls
# ---------------------------------------------------------------------------


def test_local_backend_serve_one_bit_identical_to_core(handle, key):
    p = make_params(handle.n, c=0.6, eps_a=0.1, delta=0.01)
    be = LocalBackend(handle, params=p, walk_chunk=128)
    out = be.serve_one(
        QuerySpec(kind="single_source", node=3), key,
        variant="telescoped", n_r=p.n_r,
    )
    ref = single_source(
        key, handle.g, handle.eg, 3, p, variant="telescoped", walk_chunk=128
    )
    np.testing.assert_array_equal(out["scores"], np.asarray(ref))

    out = be.serve_one(
        QuerySpec(kind="topk", node=3, k=7), key, variant="tree", n_r=p.n_r
    )
    idx, vals = topk(
        key, handle.g, handle.eg, 3, 7, p, variant="tree", walk_chunk=128
    )
    np.testing.assert_array_equal(out["topk_nodes"], np.asarray(idx))
    np.testing.assert_array_equal(out["topk_scores"], np.asarray(vals))


def test_session_default_backend_is_local_and_shares_handle(handle):
    sess = SimRankSession(handle)
    assert isinstance(sess.backend, LocalBackend)
    assert isinstance(sess.backend, Backend)  # protocol conformance
    assert sess.backend.handle is sess.handle  # epoch donation stays valid
    assert sess.backend.dispatch_label("tree") == "tree"


def test_session_accepts_backend_instance(handle):
    p = make_params(handle.n, c=0.6, eps_a=0.1, delta=0.01)
    be = LocalBackend(handle.copy(), params=p, walk_chunk=128)
    sess = SimRankSession(be, top_k=5)
    assert sess.backend is be
    assert sess.params is p  # session adopts the backend's error budget
    env = sess.query(3)
    assert env.topk_nodes.shape == (5,)


# ---------------------------------------------------------------------------
# QueryTicket async serving
# ---------------------------------------------------------------------------


def test_ticket_result_matches_drain_bitwise(handle):
    sess_a = SimRankSession(handle, seed=7, top_k=5, batch_q=4)
    sess_b = SimRankSession(handle, seed=7, top_k=5, batch_q=4)
    nodes = [1, 2, 3]
    drained = {}
    for u in nodes:
        sess_a.submit(u)
    for u, env in zip(nodes, sess_a.drain(budget_walks=64)):
        drained[u] = env
    tickets = [sess_b.submit(u) for u in nodes]
    # force out of order: the last ticket's result() serves the batch
    last = tickets[-1].result(budget_walks=64)
    for t, u in zip(tickets, nodes):
        assert t.done
        np.testing.assert_array_equal(
            t.result().topk_scores, drained[u].topk_scores
        )
        np.testing.assert_array_equal(
            t.result().topk_nodes, drained[u].topk_nodes
        )
    assert last is tickets[-1].envelope
    assert sess_b.drain() == []  # queue fully consumed by result()


def test_ticket_partial_drain_leaves_later_batches_queued(handle):
    sess = SimRankSession(handle, seed=0, top_k=5, batch_q=2)
    tickets = [sess.submit(u) for u in [1, 2, 3, 4, 5]]
    assert all(t.poll() is None for t in tickets)
    tickets[2].result(budget_walks=64)  # serves batches [1,2] and [3,4]
    assert [t.done for t in tickets] == [True, True, True, True, False]
    rest = sess.drain(budget_walks=64)
    assert len(rest) == 1 and rest[0].node == 5
    assert tickets[4].done  # drain also fills tickets
    assert sess.pending == (0, 0)


def test_epoch_fills_tickets(handle):
    sess = SimRankSession(handle, seed=0, top_k=5, batch_q=4)
    t = sess.submit(2)
    ep = sess.epoch(inserts=(np.array([0]), np.array([1])),
                    budget_walks=64)
    assert t.done and t.poll() is ep.results[0]


# ---------------------------------------------------------------------------
# ShardedBackend semantics (single shard: runs on the plain CPU test env)
# ---------------------------------------------------------------------------


def test_handle_shard_keeps_edges_and_version_coherent(handle):
    state = handle.shard(shards=1)
    assert state.version == handle.version
    s0, d0 = handle.to_host_edges()
    s1, d1 = state.to_host_edges()
    assert sorted(zip(s0.tolist(), d0.tolist())) == sorted(
        zip(s1.tolist(), d1.tolist())
    )
    # headroom from the handle's spare COO capacity carried over
    assert state.capacity_per_shard * state.shards > state.num_edges


def test_sharded_update_then_query_equals_rebuild(handle):
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = ShardedBackend(handle.shard(shards=1), params=p, walk_chunk=128)
    rng = np.random.default_rng(0)
    ins_s = rng.integers(0, handle.n, 32).astype(np.int32)
    ins_d = rng.integers(0, handle.n, 32).astype(np.int32)
    assert be.apply_ops(ins_s, ins_d, True).all()
    del_s, del_d = handle.to_host_edges()
    assert be.apply_ops(del_s[:8], del_d[:8], False).all()
    assert be.version == handle.version + 2

    s2, d2 = be.to_host_edges()
    rebuilt = ShardedBackend(
        ShardedGraphState(s2, d2, handle.n, shards=1, version=be.version),
        params=p, walk_chunk=128,
    )
    k = jnp.stack([jax.random.key(11)])
    a, _, _, _ = be.serve_batch("single_source", [3], k, n_r=192)
    b, _, _, _ = rebuilt.serve_batch("single_source", [3], k, n_r=192)
    np.testing.assert_array_equal(a, b)  # exact, not tolerance


def test_sharded_delete_semantics_one_copy_per_op(handle):
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = ShardedBackend(handle.shard(shards=1), params=p)
    # duplicate edge: two copies live after one extra insert
    s0, d0 = handle.to_host_edges()
    e = (np.array([s0[0]], np.int32), np.array([d0[0]], np.int32))
    assert be.apply_ops(*e, True).all()
    assert be.apply_ops(*e, False).all()   # removes ONE copy
    assert be.apply_ops(*e, False).all()   # removes the second
    assert not be.apply_ops(*e, False).any()  # absent now: unapplied
    assert not be.overflow  # absent deletes are not overflow


def test_sharded_delete_one_copy_per_pair_per_batch(handle):
    """Duplicate pairs inside ONE batch delete a single copy (the
    apply_update_batch contract) — only the first op reports applied."""
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = ShardedBackend(handle.shard(shards=1), params=p)
    s0, d0 = handle.to_host_edges()
    e = (np.array([s0[0]], np.int32), np.array([d0[0]], np.int32))
    assert be.apply_ops(*e, True).all()  # two live copies now
    dup = (np.array([s0[0], s0[0]], np.int32),
           np.array([d0[0], d0[0]], np.int32))
    mask = be.apply_ops(*dup, False)
    assert mask.tolist() == [True, False]
    # exactly one copy left
    assert be.apply_ops(*e, False).all()
    assert not be.apply_ops(*e, False).any()


def test_sharded_overflow_sticky_and_regrow(handle):
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    m = handle.num_edges
    state = ShardedGraphState(*handle.to_host_edges(), handle.n,
                             shards=1, capacity_per_shard=m)
    be = ShardedBackend(state, params=p)
    mask = be.apply_ops(np.array([0, 1], np.int32),
                        np.array([1, 0], np.int32), True)
    assert not mask.any() and be.overflow
    assert be.version == handle.version  # nothing applied: no bump
    be.regrow()
    assert not be.overflow
    assert state.capacity_per_shard >= 2 * m
    assert be.apply_ops(np.array([0, 1], np.int32),
                        np.array([1, 0], np.int32), True).all()


def test_session_sharded_single_shard_end_to_end(handle):
    sess = SimRankSession(handle, seed=0, top_k=5, backend="sharded",
                          shards=1, walk_chunk=128)
    env = sess.query(QuerySpec(kind="topk", node=3, budget_walks=128))
    assert env.variant == "sharded[spmd]"
    assert env.topk_nodes.shape == (5,)
    assert 3 not in env.topk_nodes.tolist()
    rep = sess.update(inserts=(np.array([0, 1]), np.array([2, 3])))
    assert rep.applied == 2 and sess.version == 1
    t = sess.submit(QuerySpec(kind="single_source", node=1,
                              budget_walks=128))
    env2 = t.result()
    assert env2.version == 1
    assert env2.scores.shape == (handle.n,)
    # the fused epoch is a backend stage now: it runs on the mesh too
    ep = sess.epoch(inserts=(np.array([2]), np.array([4])),
                    queries=[QuerySpec(kind="topk", node=3)],
                    budget_walks=64)
    assert ep.version == 2 and ep.updates_applied == 1
    assert ep.results[0].variant == "sharded[spmd]"
    assert ep.results[0].topk_nodes.shape == (5,)
    # the serve path sees the epoch's updates (host state replayed)
    env3 = sess.query(QuerySpec(kind="single_source", node=1,
                                budget_walks=128))
    assert env3.version == 2
    with pytest.raises(ValueError):
        sess.query(QuerySpec(kind="topk", node=1, variant="tree"))


def test_sharded_rejects_bad_geometry(handle):
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    with pytest.raises(ValueError, match="divisible"):
        ShardedBackend(handle.shard(shards=3), params=p)  # 1 device
    with pytest.raises(ValueError, match="probe"):
        ShardedBackend(handle.shard(shards=1), params=p, probe="nope")
    with pytest.raises(ValueError, match="frontier_dtype"):
        ShardedBackend(handle.shard(shards=1), params=p,
                       frontier_dtype="float16")
    with pytest.raises(ValueError, match="model"):
        from repro.utils.jaxcompat import make_mesh

        ShardedBackend(handle.shard(shards=1), params=p,
                       mesh=make_mesh((1,), ("data",)))


def test_session_rejects_stray_backend_args(handle):
    with pytest.raises(ValueError, match="sharded"):
        SimRankSession(handle, shards=8)  # forgot backend="sharded"
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = LocalBackend(handle.copy(), params=p)
    with pytest.raises(ValueError, match="geometry"):
        SimRankSession(be, shards=2)  # instance already carries geometry
    with pytest.raises(ValueError, match="not both"):
        SimRankSession(LocalBackend(handle.copy(), params=p),
                       backend="sharded")
    with pytest.raises(ValueError, match="own graph state"):
        # the positional handle would be silently shadowed
        SimRankSession(handle, backend=LocalBackend(handle.copy(), params=p))


def test_sharded_odd_edge_chunks_pad_cleanly(handle):
    """edge_chunks that don't divide the 1024 padding floor must still
    produce a probe-compatible m_pad."""
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = ShardedBackend(handle.shard(shards=1), params=p,
                        walk_chunk=64, edge_chunks=3)
    est, _, _, _ = be.serve_batch(
        "single_source", [3], jnp.stack([jax.random.key(0)]), n_r=64
    )
    assert est.shape == (1, handle.n)


def test_sharded_infers_shards_from_mesh(handle):
    """mesh= without shards= sizes the partition from the model extent."""
    from repro.utils.jaxcompat import make_mesh

    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    mesh = make_mesh((1, 1), ("data", "model"))
    be = ShardedBackend(handle, params=p, mesh=mesh)
    assert be.state.shards == 1 and be.mesh is mesh


def test_backend_instance_session_owns_copy_for_epochs(handle):
    """A backend advertising the epoch stage gets epochs even when the
    caller built it: the session asks it to own-copy its graph state at
    construction, so donated epoch steps never touch the caller's
    arrays (capability detection replaced the old blanket refusal)."""
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = LocalBackend(handle, params=p)
    g_src_before = np.asarray(handle.g.src).copy()
    eg_before = np.asarray(handle.eg.in_nbrs).copy()
    sess = SimRankSession(be)
    assert be.handle is not handle  # own-copied at construction
    ep = sess.epoch(inserts=(np.array([0]), np.array([1])),
                    queries=[1], budget_walks=32)
    assert ep.updates_applied == 1 and sess.version == 1
    # the caller's handle (and the arrays under it) are untouched
    np.testing.assert_array_equal(np.asarray(handle.g.src), g_src_before)
    np.testing.assert_array_equal(np.asarray(handle.eg.in_nbrs), eg_before)
    assert handle.version == 0


def test_epoch_capability_detection_refuses_without_stage(handle):
    """A backend without the epoch stage still gets the clear refusal."""

    class NoEpochBackend(LocalBackend):
        supports_epoch = False

    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    sess = SimRankSession(NoEpochBackend(handle.copy(), params=p))
    with pytest.raises(NotImplementedError, match="epoch_batch"):
        sess.epoch(queries=[1])


# ---------------------------------------------------------------------------
# Sharded fused epochs (single shard: runs on the plain CPU test env)
# ---------------------------------------------------------------------------


def _epoch_mirror_equals_rebuild(backend):
    """The carried device epoch state must be bit-identical to a
    from-scratch rebuild from the (replayed) host edge list."""
    from repro.core.epoch import build_shard_epoch_graph

    st = backend._epoch_graph
    rebuilt = build_shard_epoch_graph(
        *backend.state.to_host_edges(), backend.state.n,
        shards=backend.state.shards,
        capacity_per_shard=st.capacity, k_max=st.k_max,
    )
    for f in ("src_sh", "dst_sh", "counts", "in_nbrs", "in_deg"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st, f)), np.asarray(getattr(rebuilt, f)),
            err_msg=f"epoch mirror field {f} != rebuild",
        )


def test_sharded_epoch_mirrors_equal_rebuild(handle):
    """Insert-only then mixed insert/delete epochs through the session:
    after each, the device-resident shard buffers are bit-identical to a
    from-scratch rebuild of the updated edge list."""
    sess = SimRankSession(handle, seed=0, top_k=5, batch_q=2,
                          update_batch=16, walk_chunk=128,
                          backend="sharded", shards=1)
    s0, d0 = handle.to_host_edges()
    # insert-only epoch (the O(B) append variant)
    ep = sess.epoch(inserts=(np.array([0, 1, 2]), np.array([3, 4, 5])),
                    queries=[1, 2], budget_walks=64)
    assert ep.updates_applied == 3 and ep.version == 1
    _epoch_mirror_equals_rebuild(sess.backend)
    # mixed epoch(s) (delete compaction == rebuild); drain_epochs in case
    # the batch cutter splits at a duplicate-pair conflict
    sess.queue_update(np.array([6]), np.array([7]))
    sess.queue_update(s0[:4], d0[:4], insert=False)
    for u in (1, 2):
        sess.submit(u)
    eps = sess.drain_epochs(budget_walks=64)
    assert sum(e.updates_applied for e in eps) == 5
    _epoch_mirror_equals_rebuild(sess.backend)
    assert sess.backend.state.num_edges == len(s0) + 4 - 4


def test_sharded_epoch_scores_match_local_under_shared_keys(handle):
    """Local and sharded epochs draw bit-identical walks under shared
    keys (same sampler, same ELL rows); scores agree to float summation
    order of the two probes."""
    import jax

    key = jax.random.key(123)
    ins = (np.array([0, 1, 2, 3]), np.array([4, 5, 6, 7]))
    s0, d0 = handle.to_host_edges()

    def run(backend_kw):
        sess = SimRankSession(handle, seed=0, top_k=5, batch_q=2,
                              update_batch=16, walk_chunk=128,
                              **backend_kw)
        qs = [QuerySpec(kind="single_source", node=u,
                        key=jax.random.fold_in(key, u)) for u in (1, 3)]
        ep = sess.epoch(inserts=ins, deletes=(s0[:2], d0[:2]),
                        queries=qs, budget_walks=192)
        return np.stack([r.scores for r in ep.results])

    local = run({})
    sharded = run(dict(backend="sharded", shards=1))
    assert np.abs(local - sharded).max() < 1e-4


def test_ring_backend_epoch_stamps_spmd_variant(handle):
    """The mesh epoch always telescopes through the spmd push — a ring
    backend's epoch envelopes must say so, not claim the ring served."""
    sess = SimRankSession(handle, seed=0, top_k=5, batch_q=1,
                          update_batch=8, walk_chunk=64,
                          backend="sharded", shards=1,
                          backend_options=dict(probe="ring"))
    ep = sess.epoch(inserts=(np.array([0]), np.array([1])),
                    queries=[1], budget_walks=32)
    assert ep.results[0].variant == "sharded[spmd]"
    env = sess.query(QuerySpec(kind="topk", node=1, budget_walks=32))
    assert env.variant == "sharded[ring]"  # serve path still rings


def test_sharded_epoch_overflow_regrow_midstream(handle):
    """A mid-stream capacity overflow inside the fused mesh epoch:
    skipped inserts are re-queued, the state regrows, and the retry
    epochs land every op — nothing lost, mirrors still == rebuild."""
    m = handle.num_edges
    state = ShardedGraphState(*handle.to_host_edges(), handle.n,
                              shards=1, capacity_per_shard=m + 2)
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    be = ShardedBackend(state, params=p, walk_chunk=128)
    sess = SimRankSession(be, seed=0, top_k=5, batch_q=2, update_batch=16)
    rng = np.random.default_rng(0)
    sess.queue_update(rng.integers(0, handle.n, 40).astype(np.int32),
                      rng.integers(0, handle.n, 40).astype(np.int32))
    eps = sess.drain_epochs(budget_walks=32)
    assert any(e.regrown for e in eps)
    assert sum(e.updates_applied for e in eps) == 40
    assert be.state.num_edges == m + 40
    assert not sess.overflow  # regrow cleared the sticky flag
    _epoch_mirror_equals_rebuild(be)


def test_sharded_epoch_then_host_update_stays_coherent(handle):
    """Interleaving host-path updates (update()) with fused epochs must
    invalidate and rebuild the carried device mirror — queries after the
    mix see every op exactly once."""
    sess = SimRankSession(handle, seed=0, top_k=5, batch_q=2,
                          update_batch=16, walk_chunk=128,
                          backend="sharded", shards=1)
    sess.epoch(inserts=(np.array([0]), np.array([1])), budget_walks=32)
    rep = sess.update(inserts=(np.array([2]), np.array([3])))
    assert rep.applied == 1
    ep = sess.epoch(inserts=(np.array([4]), np.array([5])),
                    queries=[1], budget_walks=64)
    assert ep.version == 3
    assert sess.backend.state.num_edges == handle.num_edges + 3
    _epoch_mirror_equals_rebuild(sess.backend)


# ---------------------------------------------------------------------------
# Lane-batched sharded serving (single shard: runs on the plain CPU env)
# ---------------------------------------------------------------------------


def test_sharded_batched_scores_match_per_query(handle):
    """The lane-batched sharded step is a pure batching of the per-query
    step: Q queries in ONE dispatch score within 1e-6 of Q single-query
    dispatches under the same per-query lane width and keys (matched
    ``wq`` => identical lane schedule and walk streams)."""
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    nodes = [1, 2, 3, 4]
    wq = 32  # lanes per query, held fixed across both dispatch shapes
    batched = ShardedBackend(handle.shard(shards=1), params=p,
                             walk_chunk=wq * len(nodes))
    single = ShardedBackend(handle.shard(shards=1), params=p, walk_chunk=wq)
    keys = jnp.stack([jax.random.key(40 + u) for u in nodes])
    est_b, _, _, _ = batched.serve_batch("single_source", nodes, keys, n_r=96)
    for i, u in enumerate(nodes):
        est_1, _, _, _ = single.serve_batch(
            "single_source", [u], keys[i:i + 1], n_r=96
        )
        assert np.abs(est_b[i] - est_1[0]).max() < 1e-6, u


def test_sharded_serve_scores_match_local_fused(handle):
    """Sharded drain vs local fused drain under shared per-query keys:
    the same pooled sampler and lane schedule drive both, so scores agree
    to the float-summation order of the two probes."""
    key = jax.random.key(7)

    def run(backend_kw):
        sess = SimRankSession(handle, seed=0, top_k=5, batch_q=2,
                              walk_chunk=128, **backend_kw)
        for u in (1, 3):
            sess.submit(QuerySpec(kind="single_source", node=u,
                                  key=jax.random.fold_in(key, u)))
        return np.stack([r.scores for r in sess.drain(budget_walks=192)])

    local = run({})
    sharded = run(dict(backend="sharded", shards=1))
    assert np.abs(local - sharded).max() < 1e-4


def test_sharded_serving_mirror_carried_and_invalidated(handle):
    """Repeated serving reuses the carried device mirror (the epoch-path
    ShardEpochGraph, keyed on the host mutation counter); a host-path
    update invalidates it, and the rebuilt mirror is bit-identical to a
    from-scratch rebuild of the updated edge list."""
    sess = SimRankSession(handle, seed=0, top_k=5, backend="sharded",
                          shards=1, walk_chunk=128)
    sess.query(QuerySpec(kind="single_source", node=1, budget_walks=64))
    st1 = sess.backend._epoch_graph
    assert st1 is not None
    sess.query(QuerySpec(kind="single_source", node=2, budget_walks=64))
    assert sess.backend._epoch_graph is st1  # carried, not rebuilt
    rep = sess.update(inserts=(np.array([0, 1]), np.array([2, 3])))
    assert rep.applied == 2
    env = sess.query(QuerySpec(kind="single_source", node=1,
                               budget_walks=64))
    assert env.version == 1
    assert sess.backend._epoch_graph is not st1  # update invalidated it
    _epoch_mirror_equals_rebuild(sess.backend)


# ---------------------------------------------------------------------------
# Mesh parity on 8 fake XLA host devices (subprocess: XLA_FLAGS first)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.api import GraphHandle, QuerySpec, SimRankSession
from repro.api.backend import ShardedBackend, ShardedGraphState
from repro.graph import powerlaw_graph

src, dst, n = powerlaw_graph(120, 900, seed=5)
in_deg = np.bincount(dst, minlength=n)
h = GraphHandle.from_edges(src, dst, n, capacity=len(src) + 256,
                           k_max=int(in_deg.max()) + 8)
BUDGET = 8192
local = SimRankSession(h, seed=0, top_k=5, walk_chunk=512)
shard = SimRankSession(h, seed=0, top_k=5, walk_chunk=512,
                       backend="sharded", shards=4)
assert len(jax.devices()) == 8
nodes = [int(u) for u in np.where(in_deg > 0)[0][:2]]
for u in nodes:
    key = jax.random.key(100 + u)
    el = local.query(QuerySpec(kind="single_source", node=u,
                               budget_walks=BUDGET, key=key,
                               variant="telescoped"))
    es = shard.query(QuerySpec(kind="single_source", node=u,
                               budget_walks=BUDGET, key=key))
    a, b = el.scores.copy(), es.scores.copy()
    a[u] = b[u] = 0.0  # different draws: tolerance-based comparison
    assert np.abs(a - b).max() < 0.03, (u, np.abs(a - b).max())
    assert np.abs(a - b).mean() < 0.004, (u, np.abs(a - b).mean())
    tl = local.query(QuerySpec(kind="topk", node=u, k=5,
                               budget_walks=BUDGET, key=key,
                               variant="telescoped"))
    ts = shard.query(QuerySpec(kind="topk", node=u, k=5,
                               budget_walks=BUDGET, key=key))
    assert len(set(tl.topk_nodes.tolist())
               & set(ts.topk_nodes.tolist())) >= 3, u

# ring probe == spmd probe (same CSR sampler stream => near-identical)
ring = SimRankSession(h, seed=0, top_k=5, walk_chunk=512,
                      backend="sharded", shards=4,
                      backend_options=dict(probe="ring"))
key = jax.random.key(42)
es = shard.query(QuerySpec(kind="single_source", node=nodes[0],
                           budget_walks=1024, key=key))
er = ring.query(QuerySpec(kind="single_source", node=nodes[0],
                          budget_walks=1024, key=key))
assert er.variant == "sharded[ring]"
assert np.abs(es.scores - er.scores).max() < 1e-4

# ring vs spmd LANE-BATCHED parity: one 3-query dispatch on each probe
# (same pooled sampler stream, duplicate node with its own key included);
# both label the compiled step with the probe and lane count
assert shard.backend.batch_dispatch_label(3) == "sharded[spmd,Q=3]"
assert ring.backend.batch_dispatch_label(3) == "sharded[ring,Q=3]"
ub = [nodes[0], nodes[1], nodes[0]]
kb = jnp.stack([jax.random.key(200 + i) for i in range(3)])
ba, _, _, _ = shard.backend.serve_batch("single_source", ub, kb, n_r=512)
bb, _, _, _ = ring.backend.serve_batch("single_source", ub, kb, n_r=512)
assert np.abs(ba - bb).max() < 1e-4, np.abs(ba - bb).max()
print("RING_SPMD_BATCH_OK")

# sharded update -> query == rebuild-and-query (exact)
rng = np.random.default_rng(3)
shard.update(inserts=(rng.integers(0, n, 64).astype(np.int32),
                      rng.integers(0, n, 64).astype(np.int32)),
             deletes=(src[:16], dst[:16]))
assert shard.version == 2
s2, d2 = shard.backend.to_host_edges()
reb = ShardedBackend(ShardedGraphState(s2, d2, n, shards=4,
                                       version=shard.version),
                     params=shard.params, walk_chunk=512)
k = jnp.stack([jax.random.key(7)])
a, _, _, _ = shard.backend.serve_batch("single_source", [nodes[0]], k, n_r=512)
b, _, _, _ = reb.serve_batch("single_source", [nodes[0]], k, n_r=512)
assert np.array_equal(a, b)

# ring probe with a non-divisible column count: budget 65 at walk_chunk 64
# leaves a remainder chunk of ONE column, which the data axes (extent 2)
# do not divide — the per-chunk spmd fallback must serve it (previously a
# shard_map in_specs error), matching all-spmd to 1e-4
ring_odd = SimRankSession(h, seed=0, top_k=5, walk_chunk=64,
                          backend="sharded", shards=4,
                          backend_options=dict(probe="ring"))
spmd_odd = SimRankSession(h, seed=0, top_k=5, walk_chunk=64,
                          backend="sharded", shards=4)
key = jax.random.key(9)
eo = ring_odd.query(QuerySpec(kind="single_source", node=nodes[0],
                              budget_walks=65, key=key))
es = spmd_odd.query(QuerySpec(kind="single_source", node=nodes[0],
                              budget_walks=65, key=key))
assert np.abs(eo.scores - es.scores).max() < 1e-4
print("RING_REMAINDER_OK")

# --- fused mesh epochs on 4 shards --------------------------------------
from repro.core.epoch import build_shard_epoch_graph

def mirror_equals_rebuild(be):
    st = be._epoch_graph
    rebuilt = build_shard_epoch_graph(
        *be.state.to_host_edges(), be.state.n, shards=be.state.shards,
        capacity_per_shard=st.capacity, k_max=st.k_max)
    for f in ("src_sh", "dst_sh", "counts", "in_nbrs", "in_deg"):
        assert np.array_equal(np.asarray(getattr(st, f)),
                              np.asarray(getattr(rebuilt, f))), f

h2 = GraphHandle.from_edges(src, dst, n, capacity=len(src) + 256,
                            k_max=int(in_deg.max()) + 8)
eloc = SimRankSession(h2, seed=0, top_k=5, batch_q=2, update_batch=16,
                      walk_chunk=256)
eshd = SimRankSession(h2, seed=0, top_k=5, batch_q=2, update_batch=16,
                      walk_chunk=256, backend="sharded", shards=4)
ekey = jax.random.key(55)
ins = (rng.integers(0, n, 8).astype(np.int32),
       rng.integers(0, n, 8).astype(np.int32))
# insert-only epoch, shared per-query keys => bit-identical walks
qs_l = [QuerySpec(kind="single_source", node=u,
                  key=jax.random.fold_in(ekey, u)) for u in nodes[:2]]
qs_s = [QuerySpec(kind="single_source", node=u,
                  key=jax.random.fold_in(ekey, u)) for u in nodes[:2]]
el = eloc.epoch(inserts=ins, queries=qs_l, budget_walks=256)
es = eshd.epoch(inserts=ins, queries=qs_s, budget_walks=256)
assert el.updates_applied == es.updates_applied == 8
assert eshd.version == 1
la = np.stack([r.scores for r in el.results])
sa = np.stack([r.scores for r in es.results])
assert np.abs(la - sa).max() < 1e-3, np.abs(la - sa).max()
mirror_equals_rebuild(eshd.backend)
# mixed insert/delete epoch: device delete compaction == rebuild, bitwise
ins2 = (rng.integers(0, n, 4).astype(np.int32),
        rng.integers(0, n, 4).astype(np.int32))
el = eloc.epoch(inserts=ins2, deletes=(src[16:24], dst[16:24]),
                queries=[QuerySpec(kind="topk", node=nodes[0], k=5)],
                budget_walks=128)
es = eshd.epoch(inserts=ins2, deletes=(src[16:24], dst[16:24]),
                queries=[QuerySpec(kind="topk", node=nodes[0], k=5)],
                budget_walks=128)
assert el.updates_applied == es.updates_applied
assert len(set(el.results[0].topk_nodes.tolist())
           & set(es.results[0].topk_nodes.tolist())) >= 3
mirror_equals_rebuild(eshd.backend)
sl, dl = eloc.handle.to_host_edges()
ss, ds = eshd.backend.to_host_edges()
assert sorted(zip(sl.tolist(), dl.tolist())) == sorted(
    zip(ss.tolist(), ds.tolist()))
# overflow -> regrow mid-stream (update-only epochs; cheap apply steps)
m2 = eshd.backend.state.num_edges
tight = ShardedBackend(
    ShardedGraphState(*eshd.backend.to_host_edges(), n, shards=4,
                      capacity_per_shard=eshd.backend.state._counts.max()
                      + 2),
    params=eshd.params, walk_chunk=256)
tsess = SimRankSession(tight, seed=0, top_k=5, batch_q=2, update_batch=16)
tsess.queue_update(rng.integers(0, n, 40).astype(np.int32),
                   rng.integers(0, n, 40).astype(np.int32))
teps = tsess.drain_epochs()
assert any(e.regrown for e in teps)
assert sum(e.updates_applied for e in teps) == 40
assert tight.state.num_edges == m2 + 40 and not tsess.overflow
mirror_equals_rebuild(tight)
print("EPOCH_MESH_OK")
print("BACKEND_PARITY_OK")
"""


def test_sharded_backend_parity_on_fake_mesh():
    """ShardedBackend (spmd + ring) vs LocalBackend on 8 fake XLA host
    devices: tolerance-based score/topk parity, the exact
    sharded-update -> query == rebuild-and-query invariant, the ring
    remainder-chunk regression, and the fused mesh epochs (insert-only,
    mixed, overflow->regrow; mirrors == rebuild bitwise, scores vs local
    epochs under shared keys)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RING_SPMD_BATCH_OK" in out.stdout
    assert "RING_REMAINDER_OK" in out.stdout
    assert "EPOCH_MESH_OK" in out.stdout
    assert "BACKEND_PARITY_OK" in out.stdout
