"""Fused multi-query ProbeSim serving path (DESIGN.md §3).

The seed query path was host-bound: each walk chunk was two separate jitted
dispatches (``sample_walks`` then ``probe_walks_telescoped``) with a host
round-trip between chunks, every query ran alone, and every walk paid
``max_len - 1`` full-width push levels even though the mean sqrt(c)-walk is
only ~1/(1 - sqrt(c)) nodes long.  ``multi_source`` replaces all of that with
ONE compiled step per query batch:

* **query batching across the lane dimension** — Q queries share a single
  [n + 1, W] score buffer; each live query owns a contiguous block of about
  W/live lane columns (W/Q in a full batch; padding slots own none), so
  every push level is one SpMM dispatch for the whole batch;
* **pooled walk sampling** — the walk pool (n_r walks for each of the
  ``live`` real queries) is drawn inside the same jit before the first
  level.  Per-chunk sampling pays a large fixed dispatch cost (the
  ELL-table walk); pooling amortizes it;
* **compacted walk scheduling** — instead of marching all lanes through the
  same global level p (leaving columns of short/dead walks pushing zeros for
  most levels), each lane column runs the telescoped probe of *its own* walk
  at its own position.  When a column's walk finishes (position 1), its
  telescoped estimate is deposited into a per-column accumulator and the
  column is refilled with the next walk from its query's pool partition.
  Total push work drops from ``n_r * (max_len - 1)`` column-levels per query
  to ``n_r * E[len - 1]`` — the dominant term of the measured speedup;
* **baked sentinel dump row** — score buffers are allocated once with
  a dump row at row n, so sentinel scatter/gather indices need no
  clipping and no level re-pads ``scores``;
* **one push per level, picked from what the step observes**
  (:func:`push_path`) — on a TPU the fused CSR Pallas level
  (``kernels/lane_probe``) over a dst-sorted view of the COO graph, built
  once per step; elsewhere, or when the frontier does not fit VMEM, the
  XLA level (:func:`xla_level`) over the COO or ELL push graph;
* **fused epilogue** — per-query segment reduction (owned-column sum), the
  1/n_r normalization, the diagonal fix-up and ``lax.top_k`` all run inside
  the same compiled step, with the [Q, n] accumulator donated by the caller.

Per-column correctness: for a single walk of length l, the batched telescoped
probe reduces to "for p = l..2: inject e_{u_p}; prune at eps_p/sqrt(c)^(p-1);
push; mask u_{p-1}" — positions beyond l contribute nothing.  The compacted
schedule runs exactly that per-column recurrence with a per-column position
(and hence a per-column prune threshold), so each walk's estimate is
identical to its column in ``probe_walks_telescoped`` up to float summation
order (tested to 1e-5).

Randomness contract: query q's walks depend only on (keys[q], us[q]).  With
explicit per-query ``keys``, a batched call is therefore equivalent to Q
single-query calls — the property the serving engine's batched ``drain()``
relies on (and the tests assert).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import ProbeSimParams
from repro.core.probe import push_level_padded
from repro.core.walks import walk_uniforms, walks_from_uniforms
from repro.graph.structs import EllGraph, Graph

Array = jax.Array


# ---------------------------------------------------------------------------
# Lane-compaction helpers — shared by the local fused serve and the
# distributed lane probes (core/distributed.py, core/ring.py)
# ---------------------------------------------------------------------------
#
# The compacted schedule is backend-independent bookkeeping: per-lane-column
# walk positions, pool cursors and refill ranks are tiny replicated vectors,
# identical whether the score buffer is a single [n + 1, W] array (local) or
# a [rows, W] row block per mesh shard (distributed).  Keeping ONE set of
# helpers guarantees the schedules agree step-for-step, which is what makes
# batched-vs-per-query and sharded-vs-local parity tolerance-boundable by
# float summation order alone.


def lane_owner(cols, live, w: int):
    """Owning query of each of the ``w`` lane columns ``cols``: the first
    ``live`` queries deal the columns among themselves in contiguous
    blocks, in query order, of ``w // live`` columns or one more; later
    (padding) slots own none.  ``live`` may be a Python int or a traced
    int32 scalar.  With every slot live (``live == q``, ``w == q * wq``)
    it is ``cols // wq``."""
    return cols * live // w


def lane_columns(q: int, wq: int, live) -> tuple[Array, Array]:
    """Column ids [W] (W = q * wq) and the owning query of each [W]
    (:func:`lane_owner`) when the first ``live`` of the ``q`` slots are
    live; with ``live == q`` query i owns the columns
    ``[i * wq, (i + 1) * wq)``."""
    w = q * wq
    cols = jnp.arange(w)
    return cols, lane_owner(cols, live, w)


def _owner_sum(x, qid, q: int):
    """Per-query sums [Q] of an int [W] vector over the columns each owns."""
    own = qid[None, :] == jnp.arange(q)[:, None]
    return jnp.where(own, x[None, :], 0).sum(axis=1)


def _owner_rank(flag, qid, q: int):
    """For each column, the flagged columns of its owner before it.
    Owners hold contiguous blocks in query order, so this is the global
    exclusive prefix count less the flagged columns of earlier owners."""
    per_q = _owner_sum(flag, qid, q)
    return jnp.cumsum(flag) - flag - (jnp.cumsum(per_q) - per_q)[qid]


def lane_max_steps(n_r: int, max_len: int) -> int:
    """Safety-net trip bound for the compacted loop (it exits early)."""
    return n_r * max_len + max_len + 8


def lane_continue(step, pos, next_q, *, n_r: int, max_steps: int):
    """Loop-continue predicate: walks in flight or pools undrained."""
    return (step < max_steps) & (jnp.any(pos >= 1) | jnp.any(next_q < n_r))


def lane_refill(pos, widx, next_q, pool_len, qid, *, n_r):
    """Column bookkeeping for one level: finished-column detection plus
    sticky per-query refill from the pool.

    Pure [W]-vector arithmetic — no score movement — so every level
    (the CSR kernel, the XLA level, the mesh levels) shares it verbatim.
    ``qid`` [W] is each column's owner (:func:`lane_columns`, a runtime
    array when the live count is data); ``next_q`` [Q] the per-query pool
    cursor, ``n_r`` for a slot that draws nothing.  Returns
    ``(fin, pos, widx, next_q)``; ``fin`` marks the columns whose walk just
    finished (the caller deposits their scores into ``total``).  An idle
    column takes its owner's next walk by its rank among the owner's idle
    columns: refill pulls walks from each query's pool partition in pool
    order — selection is content-independent, so the estimator stays
    unbiased.
    """
    q = next_q.shape[0]
    fin = pos == 1
    pos = jnp.where(fin, 0, pos)
    idle = (pos == 0).astype(jnp.int32)
    rank = _owner_rank(idle, qid, q)
    take = (pos == 0) & (rank < (n_r - next_q)[qid])
    new_widx = qid * n_r + jnp.minimum(next_q[qid] + rank, n_r - 1)
    widx = jnp.where(take, new_widx, widx)
    pos = jnp.where(take, pool_len[new_widx], pos)
    next_q = next_q + _owner_sum(take.astype(jnp.int32), qid, q)
    return fin, pos, widx, next_q


def lane_frontier(pool, widx, pos, sentinel: int):
    """Per-column frontier for one telescoped level at each column's own
    position: ``(active, u_p, u_prev)``; inactive columns get ``sentinel``
    (the local path scatters it into the dump row, the distributed path's
    row-iota compare never matches it)."""
    active = pos >= 2
    u_p = jnp.where(active, pool[widx, jnp.maximum(pos - 1, 0)], sentinel)
    u_prev = jnp.where(active, pool[widx, jnp.maximum(pos - 2, 0)], sentinel)
    return active, u_p, u_prev


def lane_thresholds(pos, *, sqrt_c: float, eps_p: float):
    """Per-column prune threshold (pruning rule 2 at the column's level):
    ``eps_p / sqrt(c)^(pos - 1)`` as [W] f32."""
    return eps_p * jnp.power(
        jnp.float32(sqrt_c), (1 - pos).astype(jnp.float32)
    )


def push_path(g: Graph | EllGraph, width: int) -> str:
    """The push a fused serve step's probe levels run, from what the step
    can observe: over a COO push graph, ``"csr_kernel"`` where JAX runs on
    a TPU and the fp32 frontier of ``width`` lane columns fits the CSR
    kernel's on-chip budget, and ``"coo_xla"`` otherwise; over an ELL push
    graph, ``"ell_xla"``."""
    if isinstance(g, EllGraph):
        return "ell_xla"
    from repro.kernels.lane_probe.ops import csr_level_fits

    if jax.default_backend() == "tpu" and csr_level_fits(g.n, width):
        return "csr_kernel"
    return "coo_xla"


def xla_level(g, scores, total, fin, u_p, u_prev, thr, *, cols, sqrt_c,
              prune):
    """One lane-probe level in XLA ops on an [n + 1, W] buffer: deposit,
    inject, prune, push (``push_level_padded``), exclude."""
    total = total + jnp.where(fin[None, :], scores, 0.0)
    scores = jnp.where(fin[None, :], 0.0, scores)
    scores = scores.at[u_p, cols].add(1.0)  # sentinel -> dump row
    if prune:
        scores = jnp.where(scores > thr[None, :], scores, 0.0)
    scores = push_level_padded(g, scores, sqrt_c)
    scores = scores.at[u_prev, cols].set(0.0)  # exclusion mask
    return scores, total


def live_walk_pool(keys, eg: EllGraph, us, live, *, n_r: int,
                   max_len: int, sqrt_c: float) -> tuple[Array, Array]:
    """The walk pool [Q * n_r, max_len] of a serve step whose first
    ``live`` (traced int32) of the Q slots are real queries: slot i's
    n_r walks, drawn from ``keys[i]`` and materialized one slot a loop
    trip, fill rows ``[i * n_r, (i + 1) * n_r)``; a padding slot's rows
    stay sentinel ``n`` (length 0).  Each slot's walks depend only on its
    own key and source (``core.walks``), so its rows are bit for bit a
    ``sample_walks_batch`` call's, whatever the live count.  Returns
    ``(pool, walks_sampled)``, the latter the rows materialized (int32
    scalar, ``live * n_r``)."""

    def trip(i, pool):
        cont, pick = walk_uniforms(
            keys[i], n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
        )
        walks = walks_from_uniforms(eg, us[i], cont, pick)
        return jax.lax.dynamic_update_slice(pool, walks, (i * n_r, 0))

    pool = jnp.full((us.shape[0] * n_r, max_len), eg.n, jnp.int32)
    pool = jax.lax.fori_loop(0, live, trip, pool)
    return pool, jnp.int32(live) * n_r


def fused_serve_impl(
    keys: Array,  # [Q] typed PRNG keys, one stream per query
    g: Graph | EllGraph,
    eg: EllGraph,
    us: Array,  # int32 [Q]
    acc: Array,  # f32 [Q, n] donated accumulator (usually zeros)
    live: Array,  # int32 scalar: the leading slots that are real queries
    *,
    n_r: int,
    lanes_q: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    eps_t: float,
    truncation_shift: bool,
    top_k: int,
):
    """One fused serve step: sample pool -> compacted probe -> estimates.

    ``live`` (a traced int32 scalar, so one executable serves every count)
    marks the first ``live`` of the Q slots as real queries: they deal all
    W = Q * ``lanes_q`` lane columns among themselves (:func:`lane_owner`);
    the padding slots behind them own no column and sample no walks
    (:func:`live_walk_pool`), so a step's pool and levels follow the live
    queries' walks, not Q's.  Padding rows of ``est`` carry no
    estimate.  ``live == Q`` is the full schedule: query i owns the
    columns ``[i * lanes_q, (i + 1) * lanes_q)``.

    :func:`push_path` picks the level: on a TPU, when the frontier fits,
    the fused CSR kernel over a dst-sorted view of the COO graph, built
    once here (sums in CSR order: 1e-5 of the XLA level); else the XLA
    level.  The lane buffers are fp32.  Returns
    ``(acc, est, topk_idx, topk_vals, levels, lanes, walks_sampled)``; the
    top-k outputs are None when ``top_k == 0``.  ``levels`` (int32 scalar)
    counts the probe levels the step ran, ``lanes`` (int32 [Q]) the lane
    columns each slot owned, ``walks_sampled`` (int32 scalar) the walks
    the pool materialized, ``live * n_r``.
    """
    n = eg.n
    q = us.shape[0]
    wq = lanes_q
    w = q * wq
    cols, qid = lane_columns(q, wq, live)
    lanes = _owner_sum(jnp.ones(w, jnp.int32), qid, q)
    # per-query pool cursors; a padding slot starts drained
    next0 = jnp.where(jnp.arange(q) < live, 0, n_r).astype(jnp.int32)

    # --- walk pool: the live slots' n_r walks each ----------------------
    pool, walks_sampled = live_walk_pool(
        keys, eg, us, live, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
    )
    pool_len = (pool < n).sum(axis=1).astype(jnp.int32)

    # --- one probe level: deposit + inject + prune + push + exclude -------
    rows = n + 1  # score buffer rows: the graph's and the dump row
    if push_path(g, w) == "csr_kernel":
        from repro.kernels.lane_probe.ops import (
            csr_layout, csr_push_view, lane_probe_csr_level,
        )

        # the CSR view of the graph version this step serves, built once
        rows, _ = csr_layout(n)
        view = csr_push_view(g, g.inv_in_deg * sqrt_c, rows=rows)

        def level_fn(scores, total, fin, u_p, u_prev, thr):
            return lane_probe_csr_level(
                view, scores, total, fin, u_p, u_prev, thr,
                prune=eps_p > 0.0,
            )
    else:
        level_fn = partial(
            xla_level, g, cols=cols, sqrt_c=sqrt_c, prune=eps_p > 0.0
        )

    # --- compacted probe loop ---------------------------------------------
    # Per-column state: pos (current walk position; 1/0 = finished/idle),
    # widx (walk id in the flattened pool), next_q (per-query pool cursor).
    # `total` accumulates finished columns; per-query reduction happens once
    # at the end (columns are query-sticky, so per-owner sums separate).
    max_steps = lane_max_steps(n_r, max_len)

    def cond(state):
        step, pos, widx, next_q, scores, total = state
        return lane_continue(step, pos, next_q, n_r=n_r, max_steps=max_steps)

    def body(state):
        step, pos, widx, next_q, scores, total = state
        fin, pos, widx, next_q = lane_refill(
            pos, widx, next_q, pool_len, qid, n_r=n_r
        )
        # one telescoped level per active column, at its own position
        active, u_p, u_prev = lane_frontier(pool, widx, pos, n)
        thr = lane_thresholds(pos, sqrt_c=sqrt_c, eps_p=eps_p)
        scores, total = level_fn(scores, total, fin, u_p, u_prev, thr)
        pos = jnp.where(active, pos - 1, pos)
        return step + 1, pos, widx, next_q, scores, total

    state = (
        jnp.int32(0),
        jnp.zeros(w, jnp.int32),  # pos: all idle -> first iteration refills
        jnp.zeros(w, jnp.int32),  # widx
        next0,  # next_q
        jnp.zeros((rows, w), jnp.float32),  # scores (baked dump row)
        jnp.zeros((rows, w), jnp.float32),  # total (baked dump row)
    )
    levels, pos, _, _, scores, total = jax.lax.while_loop(cond, body, state)
    # safety-net flush (no-op unless max_steps was hit)
    total = total + jnp.where((pos == 1)[None, :], scores, 0.0)

    # --- per-query segment reduction + epilogue ---------------------------
    tot = total[:n]

    def by_block():  # every slot live: query i owns lane block i
        return tot.reshape(n, q, wq).sum(axis=2).T

    def by_owner():  # an exact masked sum; no matmul, which rounds on a TPU
        own = qid[None, :] == jnp.arange(q)[:, None]  # [Q, W]
        return jnp.where(own[None], tot[:, None, :], 0.0).sum(axis=2).T

    acc = acc + jax.lax.cond(live == q, by_block, by_owner)
    est = acc / n_r
    if truncation_shift:
        est = jnp.where(est > 0, est + eps_t / 2, est)
    est = est.at[jnp.arange(q), us].set(1.0)
    if top_k > 0:
        masked = est.at[jnp.arange(q), us].set(-jnp.inf)
        vals, idx = jax.lax.top_k(masked, top_k)
        return acc, est, idx, vals, levels, lanes, walks_sampled
    return acc, est, None, None, levels, lanes, walks_sampled


# The standalone jitted entry point.  ``fused_serve_impl`` stays un-jitted so
# larger fused steps can trace it inline — the dynamic epoch step
# (serving/dynamic_engine.py) composes `apply_update_batch -> fused_serve_impl`
# inside ONE jit, which a nested jitted call with donated operands would
# complicate for no benefit.
_fused_serve = partial(
    jax.jit,
    static_argnames=(
        "n_r",
        "lanes_q",
        "max_len",
        "sqrt_c",
        "eps_p",
        "eps_t",
        "truncation_shift",
        "top_k",
    ),
    donate_argnames=("acc",),
)(fused_serve_impl)


def _query_keys(key: Array | None, keys: Array | None, q: int) -> Array:
    if keys is not None:
        return keys
    if key is None:
        raise ValueError("multi_source needs `key` or per-query `keys`")
    return jax.random.split(key, q)


def _serve(key, g, eg, us, params, *, k, lanes, n_r, keys, info, live):
    """One jitted fused step for :func:`multi_source` (``k == 0``) and
    :func:`multi_source_topk`; returns ``(est, idx, vals)`` on the device
    and, when ``info`` is a dict, puts the step's probe-level count (int32
    scalar) in ``info["levels"]``, its :func:`push_path` in
    ``info["push_path"]``, the lane columns each slot owned (int32
    [Q], from the step) in ``info["lanes"]`` and the walks its pool
    materialized (int32 scalar) in ``info["walks_sampled"]``.  ``live``
    None serves every slot."""
    us = jnp.asarray(us, jnp.int32)
    q = int(us.shape[0])
    lanes_q = max(1, lanes // q)
    acc = jnp.zeros((q, g.n), jnp.float32)
    _, est, idx, vals, levels, owned, walks = _fused_serve(
        _query_keys(key, keys, q), g, eg, us, acc,
        np.int32(q if live is None else live),
        n_r=int(n_r or params.n_r),
        lanes_q=lanes_q,
        max_len=params.max_len,
        sqrt_c=params.sqrt_c,
        eps_p=params.eps_p,
        eps_t=params.eps_t,
        truncation_shift=params.truncation_shift,
        top_k=int(k),
    )
    if info is not None:
        info["levels"] = levels
        info["push_path"] = push_path(g, q * lanes_q)
        info["lanes"] = owned
        info["walks_sampled"] = walks
    return est, idx, vals


def multi_source(
    key: Array | None,
    g: Graph | EllGraph,
    eg: EllGraph,
    us: Array,
    params: ProbeSimParams,
    *,
    lanes: int = 256,
    n_r: int | None = None,
    keys: Array | None = None,
    info: dict | None = None,
    live: int | None = None,
) -> Array:
    """Fused multi-query single-source SimRank: estimates [Q, n].

    ``us`` is int32 [Q]; ``g`` is the push representation (COO or ELL), ``eg``
    the ELL table used for walk sampling.  ``lanes`` is the total lane-column
    width shared by the batch (each query owns ``lanes // Q`` columns, or
    about ``lanes / live`` under ``live``).  ``n_r`` overrides
    ``params.n_r`` (anytime/budgeted serving).  Pass per-query ``keys`` ([Q] typed key array) for
    batch-vs-serial determinism; otherwise ``key`` is split into Q streams.
    A dict passed as ``info`` receives ``"levels"``: the number of probe
    levels the step ran, as an int32 device scalar, ``"push_path"``: the
    push its levels ran (:func:`push_path`), ``"lanes"``: the lane
    columns each query owned, as an int32 [Q] device array, and
    ``"walks_sampled"``: the walks the pool materialized (``live *
    n_r``), as an int32 device scalar.  ``live``
    serves only the first ``live`` queries of ``us``: they share every
    lane column and the rest are padding with no walks and no estimate
    (``fused_serve_impl``); the count is data, so one compiled step
    serves every ``live`` for a batch shape.
    """
    est, _, _ = _serve(
        key, g, eg, us, params, k=0, lanes=lanes, n_r=n_r, keys=keys,
        info=info, live=live,
    )
    return est


def multi_source_topk(
    key: Array | None,
    g: Graph | EllGraph,
    eg: EllGraph,
    us: Array,
    k: int,
    params: ProbeSimParams,
    *,
    lanes: int = 256,
    n_r: int | None = None,
    keys: Array | None = None,
    info: dict | None = None,
    live: int | None = None,
) -> tuple[Array, Array]:
    """Fused batched top-k (paper Def. 2): (nodes [Q, k], estimates [Q, k]).

    The query node itself is excluded; ``top_k`` runs inside the same
    compiled step as sampling and the probe.  ``info`` and ``live`` as
    for :func:`multi_source`.
    """
    _, idx, vals = _serve(
        key, g, eg, us, params, k=k, lanes=lanes, n_r=n_r, keys=keys,
        info=info, live=live,
    )
    return idx, vals
