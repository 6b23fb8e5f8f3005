"""Fused multi-query serve path (core/multisource.py + serving engine).

Covers the tentpole contracts:
* the compacted fused probe equals the host-accumulated telescoped oracle,
  including partial pools (n_r not divisible by the lane width);
* ``multi_source(us=[u])`` IS ``single_source(u, variant='telescoped')``;
* batched results are identical to per-query results given per-query keys
  (the engine's batched ``drain()`` == serial serving property);
* the live queries of a short batch share every lane column (padding slots
  own none), and a full batch keeps the lane-block schedule bit for bit;
* the walk pool holds the live slots' walks only, each bit for bit what
  the all-slots pool held, so every live answer is unchanged;
* COO push, ELL push and the Pallas kernel path agree.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    make_params,
    multi_source,
    multi_source_topk,
    single_source,
    simrank_power,
)
from repro.core import multisource
from repro.core.multisource import (
    fused_serve_impl, lane_columns, lane_refill, live_walk_pool,
)
from repro.core.probe import probe_walks_telescoped
from repro.core.walks import sample_walks_batch
from repro.graph import ell_from_edges, graph_from_edges, powerlaw_graph


def _oracle(g, pool_q, u, params, n_r):
    """Host-accumulated telescoped estimate for one query's walk pool."""
    cols = probe_walks_telescoped(
        g, pool_q, sqrt_c=params.sqrt_c, eps_p=params.eps_p
    )
    ref = cols.sum(axis=1) / n_r
    if params.truncation_shift:
        ref = jnp.where(ref > 0, ref + params.eps_t / 2, ref)
    return ref.at[u].set(1.0)


@pytest.mark.parametrize("n_r,lanes", [(96, 32), (77, 32), (5, 64)])
def test_fused_equals_telescoped_oracle(toy, key, n_r, lanes):
    """Fused compacted probe == per-walk telescoped sums, for full and
    partial pools (n_r % lanes != 0 and n_r < lanes)."""
    g, eg, n = toy["g"], toy["eg"], toy["n"]
    params = make_params(n, c=0.25, eps_a=0.1, delta=0.01, n_r_override=n_r)
    us = jnp.array([0, 3], jnp.int32)
    keys = jax.random.split(key, 2)
    est = multi_source(None, g, eg, us, params, lanes=lanes, keys=keys)
    pool = sample_walks_batch(
        keys, eg, us, n_r=n_r, max_len=params.max_len, sqrt_c=params.sqrt_c
    )
    for qi in range(2):
        ref = _oracle(g, pool[qi], int(us[qi]), params, n_r)
        np.testing.assert_allclose(
            np.asarray(est[qi]), np.asarray(ref), atol=2e-5
        )


def test_single_source_is_q1_specialization(toy, key):
    params = make_params(toy["n"], c=0.25, eps_a=0.1, n_r_override=200)
    s = single_source(
        key, toy["g"], toy["eg"], 0, params, variant="telescoped", walk_chunk=32
    )
    m = multi_source(
        key, toy["g"], toy["eg"], jnp.array([0]), params, lanes=32
    )[0]
    np.testing.assert_allclose(np.asarray(s), np.asarray(m), atol=1e-5)
    assert float(s[0]) == 1.0


def test_batch_matches_per_query(small_powerlaw, key):
    """Q = 4 batch == 4 single-query calls with the same per-query keys."""
    g, eg = small_powerlaw["g"], small_powerlaw["eg"]
    params = make_params(small_powerlaw["n"], c=0.6, eps_a=0.2,
                         n_r_override=150)
    in_deg = np.asarray(g.in_deg)
    us = np.argsort(-in_deg)[:4].astype(np.int32)
    keys = jax.random.split(key, 4)
    batch = multi_source(None, g, eg, us, params, lanes=64, keys=keys)
    for i in range(4):
        solo = multi_source(
            None, g, eg, us[i : i + 1], params, lanes=64, keys=keys[i : i + 1]
        )
        np.testing.assert_allclose(
            np.asarray(batch[i]), np.asarray(solo[0]), atol=1e-5
        )


def _padded(us, keys, live):
    """A batch of len(us) slots: the first ``live`` queries, then repeats
    of the last live one (the session's padding)."""
    q = len(us)
    idx = np.minimum(np.arange(q), live - 1)
    return jnp.asarray(np.asarray(us)[idx], jnp.int32), keys[idx]


@pytest.mark.parametrize("live", [1, 2, 3])
def test_short_batch_matches_solo(small_powerlaw, key, live):
    """Live queries of a short batch share every lane column; each one's
    estimate equals that query served alone up to the order of f32 sums,
    and the padding slots own no column."""
    g, eg = small_powerlaw["g"], small_powerlaw["eg"]
    params = make_params(small_powerlaw["n"], c=0.6, eps_a=0.2,
                         n_r_override=150)
    us = np.argsort(-np.asarray(g.in_deg))[:4].astype(np.int32)
    keys = jax.random.split(key, 4)
    pus, pkeys = _padded(us, keys, live)
    info = {}
    batch = multi_source(None, g, eg, pus, params, lanes=64, keys=pkeys,
                         live=live, info=info)
    lanes = np.asarray(info["lanes"]).tolist()
    assert sum(lanes) == 64
    assert lanes[live:] == [0] * (4 - live)
    assert min(lanes[:live]) == 64 // live
    for i in range(live):
        solo = multi_source(
            None, g, eg, us[i : i + 1], params, lanes=64, keys=keys[i : i + 1]
        )
        np.testing.assert_allclose(
            np.asarray(batch[i]), np.asarray(solo[0]), atol=1e-5
        )


def _block_refill(pos, widx, next_q, pool_len, *, q, wq, n_r):
    """The every-slot-live refill as lane blocks: query i owns the columns
    [i * wq, (i + 1) * wq), ranks and takes counted per block."""
    w = q * wq
    qid = jnp.arange(w) // wq
    fin = pos == 1
    pos = jnp.where(fin, 0, pos)
    idle = (pos == 0).astype(jnp.int32).reshape(q, wq)
    rank = (jnp.cumsum(idle, axis=1) - idle).reshape(w)
    take = (pos == 0) & (rank < (n_r - next_q)[qid])
    new_widx = qid * n_r + jnp.minimum(next_q[qid] + rank, n_r - 1)
    widx = jnp.where(take, new_widx, widx)
    pos = jnp.where(take, pool_len[new_widx], pos)
    next_q = next_q + take.astype(jnp.int32).reshape(q, wq).sum(axis=1)
    return fin, pos, widx, next_q


def test_full_batch_keeps_the_block_schedule(small_powerlaw, key):
    """live == Q is the lane-block schedule bit for bit: its owners are
    ``cols // wq``, the refill takes the same walks into the same columns
    level after level, and the step reports W / Q columns a query."""
    q, wq, n_r = 4, 16, 40
    cols, qid = lane_columns(q, wq, jnp.int32(q))
    assert np.array_equal(np.asarray(qid), np.asarray(cols) // wq)
    rng = np.random.default_rng(0)
    pool_len = jnp.asarray(rng.integers(1, 6, q * n_r), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 4, q * wq), jnp.int32)
    widx = jnp.asarray(rng.integers(0, q * n_r, q * wq), jnp.int32)
    next_q = jnp.asarray(rng.integers(0, n_r, q), jnp.int32)
    a = b = (pos, widx, next_q)
    for _ in range(60):  # drains every pool: the tail levels too
        fa, *a = lane_refill(*a, pool_len, qid, n_r=n_r)
        fb, *b = _block_refill(*b, pool_len, q=q, wq=wq, n_r=n_r)
        for x, y in zip([fa, *a], [fb, *b]):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        a[0] = jnp.where(a[0] >= 1, a[0] - 1, a[0])
        b[0] = jnp.where(b[0] >= 1, b[0] - 1, b[0])
    assert np.asarray(a[2]).tolist() == [n_r] * q

    g, eg = small_powerlaw["g"], small_powerlaw["eg"]
    params = make_params(small_powerlaw["n"], c=0.6, eps_a=0.2,
                         n_r_override=150)
    us = jnp.asarray(np.argsort(-np.asarray(g.in_deg))[:4], jnp.int32)
    info = {}
    multi_source_topk(None, g, eg, us, 5, params, lanes=64,
                      keys=jax.random.split(key, 4), live=4, info=info)
    assert np.asarray(info["lanes"]).tolist() == [16] * 4


def test_live_one_step_runs_its_own_levels(small_powerlaw, key):
    """A lone live query runs at most ceil(column-levels / W) + max_len
    levels, W = every lane column; the padded schedule ran W / Q."""
    g, eg, n = small_powerlaw["g"], small_powerlaw["eg"], small_powerlaw["n"]
    params = make_params(n, c=0.6, eps_a=0.2, n_r_override=600)
    u = int(np.argmax(np.asarray(g.in_deg)))
    keys = jnp.stack([key] * 4)
    us = jnp.full((4,), u, jnp.int32)
    lone, padded = {}, {}
    multi_source(None, g, eg, us, params, lanes=64, keys=keys, live=1,
                 info=lone)
    multi_source(None, g, eg, us, params, lanes=64, keys=keys, info=padded)
    pool = sample_walks_batch(keys[:1], eg, us[:1], n_r=600,
                              max_len=params.max_len, sqrt_c=params.sqrt_c)
    # a walk of l nodes holds its column for max(l - 1, 1) levels
    held = int(np.maximum((np.asarray(pool) < n).sum(-1) - 1, 1).sum())
    assert int(lone["levels"]) <= -(-held // 64) + params.max_len
    assert int(lone["levels"]) < int(padded["levels"])


def _all_slots_pool(keys, eg, us, live, *, n_r, max_len, sqrt_c):
    """Every slot's n_r walks, padding slots' too: the pool the step
    sampled before it sampled the live slots only."""
    pool = sample_walks_batch(keys, eg, us, n_r=n_r, max_len=max_len,
                              sqrt_c=sqrt_c)
    return pool.reshape(-1, max_len), jnp.int32(us.shape[0] * n_r)


@pytest.mark.parametrize("live", [1, 2, 4])
def test_pool_samples_the_live_slots_only(small_powerlaw, key, monkeypatch,
                                          live):
    """The step materializes n_r walks for each live slot and none for
    the padding: each live slot's rows are ``sample_walks_batch``'s bit
    for bit, padding rows are sentinel, and every live estimate, and the
    level count, equal the same step over the all-slots pool bit for
    bit."""
    g, eg, n = small_powerlaw["g"], small_powerlaw["eg"], small_powerlaw["n"]
    q, n_r = 4, 150
    params = make_params(n, c=0.6, eps_a=0.2, n_r_override=n_r)
    us = np.argsort(-np.asarray(g.in_deg))[:q].astype(np.int32)
    pus, pkeys = _padded(us, jax.random.split(key, q), live)
    walk = dict(n_r=n_r, max_len=params.max_len, sqrt_c=params.sqrt_c)

    pool, sampled = live_walk_pool(pkeys, eg, pus, jnp.int32(live), **walk)
    assert int(sampled) == live * n_r
    pool = np.asarray(pool).reshape(q, n_r, params.max_len)
    ref = sample_walks_batch(pkeys[:live], eg, pus[:live], **walk)
    assert np.array_equal(pool[:live], np.asarray(ref))
    assert (pool[live:] == n).all()

    static = dict(
        n_r=n_r, lanes_q=64 // q, max_len=params.max_len,
        sqrt_c=params.sqrt_c, eps_p=params.eps_p, eps_t=params.eps_t,
        truncation_shift=params.truncation_shift, top_k=0,
    )

    def step():  # traced afresh, so it picks up the pool in place now
        acc = jnp.zeros((q, n), jnp.float32)
        return jax.jit(lambda *a: fused_serve_impl(*a, **static))(
            pkeys, g, eg, pus, acc, jnp.int32(live)
        )

    _, est, _, _, levels, _, walks = step()
    assert int(walks) == live * n_r
    monkeypatch.setattr(multisource, "live_walk_pool", _all_slots_pool)
    _, ref_est, _, _, ref_levels, _, ref_walks = step()
    assert int(ref_walks) == q * n_r
    assert int(levels) == int(ref_levels)
    assert np.array_equal(np.asarray(est)[:live], np.asarray(ref_est)[:live])


def test_padding_slots_claim_no_column():
    """The refill never hands a padding slot's walk to a column, and a
    padding slot's pool cursor stays drained."""
    q, wq, n_r, live = 4, 8, 5, 3
    _, qid = lane_columns(q, wq, jnp.int32(live))
    assert np.bincount(np.asarray(qid), minlength=q).tolist() == [11, 11, 10, 0]
    assert set(np.asarray(qid).tolist()) == {0, 1, 2}
    pos = widx = jnp.zeros(q * wq, jnp.int32)
    next_q = jnp.where(jnp.arange(q) < live, 0, n_r).astype(jnp.int32)
    pool_len = jnp.full(q * n_r, 3, jnp.int32)
    _, pos, widx, next_q = lane_refill(pos, widx, next_q, pool_len, qid,
                                       n_r=n_r)
    taken = np.asarray(widx)[np.asarray(pos) > 0]
    assert (taken // n_r < live).all()
    assert np.asarray(next_q).tolist() == [n_r] * q  # 11, 11, 10 >= 5
    assert len(taken) == live * n_r and len(set(taken.tolist())) == len(taken)


def test_fused_error_bound_toy(toy, key):
    """The fused path stays within the Thm 2 bound on the paper's graph."""
    truth = np.asarray(simrank_power(toy["g"], c=0.25, iters=60))[0]
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01)
    est = np.asarray(
        multi_source(key, toy["g"], toy["eg"], jnp.array([0]), params,
                     lanes=256)
    )[0]
    err = np.abs(est - truth)
    err[0] = 0
    assert err.max() <= params.eps_a, f"maxerr {err.max()}"


def test_push_representations_agree(key):
    """The COO and ELL push graphs give one answer."""
    src, dst, n = powerlaw_graph(128, 600, seed=1)  # n tiles by block_rows
    g = graph_from_edges(src, dst, n)
    eg = ell_from_edges(src, dst, n)
    params = make_params(n, c=0.6, eps_a=0.2, n_r_override=128)
    u = int(np.argmax(np.bincount(dst, minlength=n)))
    us = jnp.array([u], jnp.int32)
    coo = multi_source(key, g, eg, us, params, lanes=32)
    ell = multi_source(key, eg, eg, us, params, lanes=32)
    np.testing.assert_allclose(np.asarray(coo), np.asarray(ell), atol=1e-6)


def test_multi_source_topk_excludes_self(toy, key):
    params = make_params(toy["n"], c=0.25, eps_a=0.1, n_r_override=300)
    us = jnp.array([0, 2], jnp.int32)
    idx, vals = multi_source_topk(key, toy["g"], toy["eg"], us, 3, params)
    assert idx.shape == (2, 3) and vals.shape == (2, 3)
    for qi in range(2):
        assert int(us[qi]) not in np.asarray(idx[qi])
        assert (np.diff(np.asarray(vals[qi])) <= 1e-7).all()  # sorted


def test_tree_variant_partial_chunk(toy, key):
    """Host chunk loops sample exactly the remaining walks in the final
    partial chunk (no surplus sampling + masking) and stay accurate."""
    truth = np.asarray(simrank_power(toy["g"], c=0.25, iters=60))[0]
    params = make_params(toy["n"], c=0.25, eps_a=0.1, delta=0.01,
                         n_r_override=1000)  # 1000 = 3 * 384 + 232: partial
    est = np.asarray(
        single_source(key, toy["g"], toy["eg"], 0, params, variant="tree",
                      walk_chunk=384)
    )
    err = np.abs(est - truth)
    err[0] = 0
    assert err.max() <= params.eps_a + 0.05  # statistical headroom at n_r=1e3


def test_engine_drain_batched_matches_serial():
    """drain() in fused batches == the same queries served one at a time.

    Queries carry their PRNG stream from submit time, so batch composition
    (including repeat padding of the final short batch) cannot change any
    answer."""
    from repro.serving.engine import SimRankEngine

    src, dst, n = powerlaw_graph(300, 2500, seed=0)
    in_deg = np.bincount(dst, minlength=n)
    g = graph_from_edges(src, dst, n, capacity=len(src) + 64)
    eg = ell_from_edges(src, dst, n, k_max=int(in_deg.max()) + 8)
    qs = np.argsort(-in_deg)[:5].astype(int)  # 5 queries, batch_q=4: padding

    eng_a = SimRankEngine(g, eg, eps_a=0.2, top_k=5, walk_chunk=128,
                          batch_q=4, seed=7)
    for u in qs:
        eng_a.submit(int(u))
    batched = eng_a.drain(budget_walks=96)

    eng_b = SimRankEngine(g, eg, eps_a=0.2, top_k=5, walk_chunk=128,
                          batch_q=1, seed=7)
    for u in qs:
        eng_b.submit(int(u))
    serial = eng_b.drain(budget_walks=96)

    assert [r.node for r in batched] == list(qs)
    for rb, rs in zip(batched, serial):
        assert rb.node == rs.node
        np.testing.assert_allclose(rb.topk_scores, rs.topk_scores, atol=1e-5)
        assert set(rb.topk_nodes) == set(rs.topk_nodes)
    assert eng_a.stats.queries == 5
    assert eng_a.stats.steps == 2  # ceil(5 / 4) fused dispatches


def test_engine_run_query_and_updates():
    """run_query + interleaved updates on the fused engine (seed semantics)."""
    from repro.serving.engine import SimRankEngine

    src, dst, n = powerlaw_graph(300, 2500, seed=0)
    in_deg = np.bincount(dst, minlength=n)
    g = graph_from_edges(src, dst, n, capacity=len(src) + 64)
    eg = ell_from_edges(src, dst, n, k_max=int(in_deg.max()) + 8)
    eng = SimRankEngine(g, eg, eps_a=0.2, top_k=5, walk_chunk=128)
    u = int(np.argmax(in_deg))
    res = eng.run_query(u, budget_walks=256)
    assert len(res.topk_nodes) == 5
    assert u not in res.topk_nodes
    eng.insert(np.array([1, 2], np.int32), np.array([u, u], np.int32))
    res2 = eng.run_query(u, budget_walks=256)
    assert len(res2.topk_nodes) == 5
    assert eng.stats.updates == 2 and eng.stats.queries == 2
