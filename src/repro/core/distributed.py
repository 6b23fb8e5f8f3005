"""Distributed ProbeSim — the multi-pod serving path.

Layout (production mesh ("pod", "data", "model")):

* graph: in-CSR offsets + in-degrees row-sharded on ``model``; the flat
  ``indices``/COO ``src``/``dst`` edge arrays sharded over all axes (they are
  the bulk of the footprint: m * 12 B);
* score frontier S [n_pad, Q*B]: rows on ``model``, walk columns on
  ``data`` (2-D sharding keeps the per-device block ~100s of MB at
  billion-edge scale);
* queries on ``data`` via the column dimension.

This module is the *baseline* distribution: pjit + sharding constraints,
letting the SPMD partitioner place the collectives (recorded by the
roofline).  The §Perf hillclimb adds a manual shard_map ring variant
(`probe_level_ring`) that pipelines the source-score exchange with the
per-block gather/scatter compute.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import constrain, mesh_axis_names
from repro.utils.pytree import static, struct

Array = jax.Array


@struct
class ShardedGraph:
    """Device-resident graph for distributed ProbeSim."""

    indptr: Array  # int32 [n_pad] in-CSR start offset per node (m < 2^31;
    #   friendster-scale (m=2.6e9) requires int64 + jax_enable_x64)
    in_deg: Array  # int32 [n_pad]
    indices: Array  # int32 [m_pad] in-neighbor lists (CSR values)
    src: Array  # int32 [m_pad] COO (for the push)
    dst: Array  # int32 [m_pad]
    n: int = static()
    n_pad: int = static()
    m: int = static()
    m_pad: int = static()

    @property
    def inv_in_deg(self) -> Array:
        d = self.in_deg.astype(jnp.float32)
        return jnp.where(d > 0, 1.0 / jnp.maximum(d, 1.0), 0.0)


def build_sharded_graph(
    src: np.ndarray, dst: np.ndarray, n: int, *, pad_nodes: int = 1,
    pad_edges: int = 1,
) -> ShardedGraph:
    """Host-side constructor (also used with ShapeDtypeStruct for dry-run)."""
    m = len(src)
    n_pad = ((n + pad_nodes - 1) // pad_nodes) * pad_nodes
    m_pad = ((m + pad_edges - 1) // pad_edges) * pad_edges
    order = np.argsort(dst, kind="stable")
    indices = np.full(m_pad, n_pad, dtype=np.int32)
    indices[:m] = src[order]
    in_deg = np.zeros(n_pad, dtype=np.int32)
    cnt = np.bincount(dst, minlength=n)
    in_deg[:n] = cnt[:n]
    indptr = np.zeros(n_pad, dtype=np.int32)
    np.cumsum(cnt[: n - 1], out=indptr[1:n])
    src_p = np.full(m_pad, n_pad, dtype=np.int32)
    dst_p = np.full(m_pad, n_pad, dtype=np.int32)
    src_p[:m] = src
    dst_p[:m] = dst
    return ShardedGraph(
        indptr=jnp.asarray(indptr),
        in_deg=jnp.asarray(in_deg),
        indices=jnp.asarray(indices),
        src=jnp.asarray(src_p),
        dst=jnp.asarray(dst_p),
        n=n, n_pad=n_pad, m=m, m_pad=m_pad,
    )


def graph_specs(sg: ShardedGraph) -> ShardedGraph:
    """PartitionSpec pytree matching ShardedGraph (static fields copied —
    pytree treedefs include the static metadata)."""
    tp = "model" if "model" in mesh_axis_names() else None
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh_axis_names())
    return ShardedGraph(
        indptr=P(tp),
        in_deg=P(tp),
        indices=P(all_axes if all_axes else None),
        src=P(all_axes if all_axes else None),
        dst=P(all_axes if all_axes else None),
        n=sg.n, n_pad=sg.n_pad, m=sg.m, m_pad=sg.m_pad,
    )


# ---------------------------------------------------------------------------
# Distributed walk sampling (CSR gathers; frontier is tiny and replicated)
# ---------------------------------------------------------------------------


def sample_walks_sharded(
    key: Array,
    sg: ShardedGraph,
    queries: Array,  # int32 [Q]
    *,
    walks_per_query: int,
    max_len: int,
    sqrt_c: float,
) -> Array:
    """Returns walks int32 [Q * B, max_len] (sentinel = n_pad)."""
    Q = queries.shape[0]
    B = walks_per_query
    n_pad = sg.n_pad
    cur = jnp.repeat(queries, B).astype(jnp.int32)  # [Q*B]
    k_cont, k_pick = jax.random.split(key)
    cont = jax.random.uniform(k_cont, (max_len - 1, Q * B)) < sqrt_c
    pick = jax.random.uniform(k_pick, (max_len - 1, Q * B))

    def step(carry, inputs):
        cur, alive = carry
        cont_t, pick_t = inputs
        cc = cur.clip(0, n_pad - 1)
        deg = sg.in_deg[cc]
        start = sg.indptr[cc]
        can = alive & cont_t & (deg > 0)
        k = jnp.floor(pick_t * deg.astype(jnp.float32)).astype(jnp.int32)
        k = k.clip(0, jnp.maximum(deg - 1, 0))
        g = (start + k).clip(0, sg.indices.shape[0] - 1)
        nxt = sg.indices[g]
        nxt = jnp.where(can, nxt, n_pad)
        return (nxt, can), nxt

    (_, _), cols = jax.lax.scan(
        step, (cur, jnp.ones(Q * B, bool)), (cont, pick)
    )
    return jnp.concatenate([cur[None, :], cols], axis=0).T  # [Q*B, L]


# ---------------------------------------------------------------------------
# Distributed telescoped probe (edge-chunked COO pushes)
# ---------------------------------------------------------------------------


def _push_chunked(
    sg: ShardedGraph, scores: Array, sqrt_c: float, edge_chunks: int
) -> Array:
    """scores [rows_total, C] -> pushed [rows_total, C] over edge chunks."""
    n_pad = sg.n_pad
    C = scores.shape[1]
    m_pad = sg.m_pad
    assert m_pad % edge_chunks == 0
    mc = m_pad // edge_chunks
    src = sg.src.reshape(edge_chunks, mc)
    dst = sg.dst.reshape(edge_chunks, mc)

    # python loop (not lax.scan): cost_analysis counts loop bodies once,
    # and the dry-run's flop/collective numbers must see every chunk
    rows_total = scores.shape[0]
    acc = jnp.zeros_like(scores)
    for ci in range(edge_chunks):
        msgs = scores[src[ci].clip(0, n_pad)]  # [mc, C]; sentinel row zero
        msgs = constrain(msgs, "tp", "dp")
        acc = acc + jax.ops.segment_sum(
            msgs, dst[ci], num_segments=rows_total
        )
    w = jnp.concatenate([
        sg.inv_in_deg,
        jnp.zeros((rows_total - n_pad,), jnp.float32),
    ]) * sqrt_c
    return acc * w[:, None]


def probe_walks_sharded(
    sg: ShardedGraph,
    walks: Array,  # [C, L] (C = Q*B columns)
    *,
    sqrt_c: float,
    eps_p: float = 0.0,
    edge_chunks: int = 8,
) -> Array:
    """Telescoped batched probe with 2-D-sharded scores; returns [n_pad, C].

    Injections and exclusion masks are *broadcast-compare* arithmetic (a row
    iota against the per-column walk node), not scatters: elementwise ops
    partition trivially under 2-D sharding, where (row, col)-indexed scatters
    trip the SPMD partitioner and serialize on TPU.
    The score matrix carries one extra padding row-block; row ``n_pad`` is
    the sentinel dump row (always zero)."""
    n_pad = sg.n_pad
    C, L = walks.shape
    rows_total = n_pad + _row_pad(sg)
    rows = jax.lax.broadcasted_iota(jnp.int32, (rows_total, C), 0)
    scores = jnp.zeros((rows_total, C), jnp.float32)
    scores = constrain(scores, "tp", "dp")
    for p in range(L, 1, -1):
        u_p = walks[:, p - 1]  # sentinel (>= n_pad) never matches a live row
        u_prev = walks[:, p - 2]
        scores = scores + (rows == u_p[None, :]).astype(jnp.float32)
        if eps_p > 0.0:
            thresh = eps_p / (sqrt_c ** (p - 1))
            scores = jnp.where(scores > thresh, scores, 0.0)
        scores = _push_chunked(sg, scores, sqrt_c, edge_chunks)
        scores = jnp.where(rows == u_prev[None, :], 0.0, scores)
        scores = constrain(scores, "tp", "dp")
    return scores[:n_pad]


# ---------------------------------------------------------------------------
# Lane-batched distributed probe (compacted schedule inside shard_map)
# ---------------------------------------------------------------------------


def lane_level_xla(push_block, *, row0, rows, w, eps_p: float):
    """Build the XLA level function for one shard's [rows, W] block.

    The level is the same deposit + inject + prune + push + exclude
    sequence the local serve runs, with injection/exclusion as row-iota
    compares (elementwise — no cross-shard scatters).  ``push_block``
    performs one renormalized push level over the full graph for this row
    block (all-gather or ring exchange — the caller owns the collective
    pattern).
    """
    rid = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0) + row0

    def level_fn(scores, total, fin, u_p, u_prev, thr):
        total = total + jnp.where(fin[None, :], scores, 0.0)
        scores = jnp.where(fin[None, :], 0.0, scores)
        scores = scores + (rid == u_p[None, :]).astype(jnp.float32)
        if eps_p > 0.0:
            scores = jnp.where(scores > thr[None, :], scores, 0.0)
        scores = push_block(scores)
        scores = jnp.where(rid == u_prev[None, :], 0.0, scores)
        return scores, total

    return level_fn


def lane_probe_block(
    level_fn,
    pool: Array,  # int32 [Q*n_r, L] replicated walk pool (sentinel >= n)
    pool_len: Array,  # int32 [Q*n_r] replicated
    *,
    rows: int,
    q: int,
    wq: int,
    n_r: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    sentinel: int,
) -> Array:
    """Compacted lane probe over ONE row block; returns ``total`` [rows, W].

    The distributed counterpart of ``fused_serve_impl``'s loop: the same
    shared lane-compaction bookkeeping (``core.multisource``) drives a
    caller-supplied level function.  The bookkeeping operands
    (``pool_len``, cursors, positions) are replicated, so every shard takes
    the identical trip count and the collectives inside ``level_fn`` line
    up across the mesh.

    ``level_fn(scores, total, fin, u_p, u_prev, thr) -> (scores, total)``
    executes one full probe level — deposit of finishing columns, unit
    injection at ``u_p``, pruning at ``thr``, the renormalized push, and
    the ``u_prev`` exclusion (``lane_level_xla`` over the caller's push).
    ``sentinel`` is the pool's walk-end marker; sentinel ids either hit a
    padding row (whose pushed mass is sliced away by the caller's ``[:n]``)
    or nothing.
    """
    from repro.core.multisource import (
        lane_columns,
        lane_continue,
        lane_frontier,
        lane_max_steps,
        lane_refill,
        lane_thresholds,
    )

    w = q * wq
    _, qid = lane_columns(q, wq, q)
    max_steps = lane_max_steps(n_r, max_len)

    def cond(state):
        step, pos, widx, next_q, scores, total = state
        return lane_continue(step, pos, next_q, n_r=n_r, max_steps=max_steps)

    def body(state):
        step, pos, widx, next_q, scores, total = state
        fin, pos, widx, next_q = lane_refill(
            pos, widx, next_q, pool_len, qid, n_r=n_r
        )
        active, u_p, u_prev = lane_frontier(pool, widx, pos, sentinel)
        thr = lane_thresholds(pos, sqrt_c=sqrt_c, eps_p=eps_p)
        scores, total = level_fn(scores, total, fin, u_p, u_prev, thr)
        pos = jnp.where(active, pos - 1, pos)
        return step + 1, pos, widx, next_q, scores, total

    # the frontier blocks differ per model shard: their initial carries
    # must be typed as varying over "model" to match the loop body
    block = jax.lax.pcast(
        jnp.zeros((rows, w), jnp.float32), "model", to="varying"
    )
    state = (
        jnp.int32(0),
        jnp.zeros(w, jnp.int32),  # pos: all idle -> first iteration refills
        jnp.zeros(w, jnp.int32),  # widx
        jnp.zeros(q, jnp.int32),  # next_q
        block,  # scores block
        block,  # total block
    )
    step, pos, _, _, scores, total = jax.lax.while_loop(cond, body, state)
    # safety-net flush (no-op unless max_steps was hit)
    return total + jnp.where((pos == 1)[None, :], scores, 0.0)


def probe_lanes_sharded(
    src_sh: Array,  # int32 [S, E] global src ids per shard (sentinel n_pad)
    dst_sh: Array,  # int32 [S, E] global dst ids per shard (sentinel n_pad)
    counts: Array,  # int32 [S] live edges per shard (prefix of the buffer)
    w_full: Array,  # f32 [n_pad] sqrt(c)/in_deg renorm weights (0 if deg 0)
    pool: Array,  # int32 [Q*n_r, L] replicated (sentinel n — ELL sampler)
    pool_len: Array,  # int32 [Q*n_r] replicated
    mesh,
    *,
    n_pad: int,
    rows: int,
    q: int,
    wq: int,
    n_r: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    sentinel: int,
    edge_chunk: int = 2048,
    frontier_dtype: str = "float32",
) -> Array:
    """Lane-batched telescoped probe, all-gather push; returns [n_pad, W].

    One fully-manual shard_map program: each model shard runs the compacted
    lane loop over its own [rows, W] frontier block; a push level all-gathers
    the frontier once, gathers its resident COO bucket's source rows and
    segment-sums into its destination rows.  Lane columns are REPLICATED over
    the data axes (the batch is one program — no per-chunk column sharding,
    hence no divisibility constraint on Q*W).

    The push walks each shard's bucket in fixed-width slices (width
    ``max(edge_chunk, E/8)``) with a per-shard dynamic trip count — live edges
    are a prefix of the buffer (FIFO compaction), so capacity padding and
    dst-skew headroom cost nothing: total gather/scatter work is the LIVE
    edge count, not shards x max-bucket capacity.  The dynamic bound is
    safe under shard_map because no collective sits inside the chunk loop
    (the all-gather happens once per level, before it); shards with fewer
    edges simply finish their level sooner.  Sentinel slots inside the last
    live chunk gather a garbage row but scatter into the dropped segment
    ``rows`` (their dst is the sentinel), so no zero-row append is needed.

    ``frontier_dtype="bfloat16"`` halves the per-level
    all_gather wire volume (the dominant collective, ROADMAP): the frontier
    is rounded to bf16 and bitcast to uint16 for the exchange (the same
    wire trick as ``core/ring.py``), then widened back — accumulation,
    deposits and the carried block stay fp32, and the single-shard
    degenerate path skips the exchange (and the rounding) entirely.
    """
    # sort each shard's bucket by source id, once per serve call: the push
    # gathers frontier rows in ascending-address order (cache-line reuse on
    # the [n_pad, W] gathered table) instead of FIFO-random, and sentinel
    # slots (src = n_pad) sort to the tail so the live prefix the chunk
    # loop relies on is preserved.  The carried mirror itself stays FIFO —
    # this is a derived view inside the compiled step, so epoch-path
    # bitwise invariants are untouched.
    perm = jnp.argsort(src_sh, axis=1)
    src_sh = jnp.take_along_axis(src_sh, perm, axis=1)
    dst_sh = jnp.take_along_axis(dst_sh, perm, axis=1)

    E = src_sh.shape[1]
    # edge_chunk is a FLOOR on the slice width, not the width itself: the
    # chunk loop's job is skipping dead tail slots on skewed shards, and
    # its granularity only needs to resolve the count skew.  Tiny chunks
    # are pure overhead (each one re-touches the [rows+1, W] accumulator:
    # at 1 shard a 2048-wide chunking of a 90k-edge bucket measured 2.3x
    # slower than one whole-bucket segment_sum), so cap the trip count at
    # ~8 and let the width grow with the bucket.
    ch = min(max(edge_chunk, -(-E // 8)), E)
    e_pad = -(-E // ch) * ch
    if e_pad != E:
        fill = jnp.full((src_sh.shape[0], e_pad - E), n_pad, jnp.int32)
        src_sh = jnp.concatenate([src_sh, fill], axis=1)
        dst_sh = jnp.concatenate([dst_sh, fill], axis=1)

    wire_bf16 = frontier_dtype == "bfloat16"

    def _exchange(scores):
        """Per-level frontier all_gather, optionally on a bf16 wire."""
        if rows == n_pad:
            # one model shard owns every row: the local block IS the full
            # frontier, and the degenerate all_gather is a pure [n_pad, W]
            # copy per level — skip it (no bf16 rounding either: the wire
            # format only exists where there is a wire)
            return scores
        if wire_bf16:
            bits = jax.lax.bitcast_convert_type(
                scores.astype(jnp.bfloat16), jnp.uint16
            )
            bits = jax.lax.all_gather(bits, "model", axis=0, tiled=True)
            return jax.lax.bitcast_convert_type(
                bits, jnp.bfloat16
            ).astype(jnp.float32)
        return jax.lax.all_gather(scores, "model", axis=0, tiled=True)

    def local(src_b, dst_b, cnt_b, w_l, pool_l, plen_l):
        # src_b/dst_b [1, e_pad]; cnt_b [1]; w_l [rows]; pool replicated
        me = jax.lax.axis_index("model")
        row0 = me * rows
        # clip into the real row range: sentinel srcs read a garbage
        # row whose message lands in the dropped segment (sentinel dst)
        sb = src_b[0].clip(0, n_pad - 1)
        db = (dst_b[0] - row0).clip(0, rows)
        n_chunks = (cnt_b[0] + ch - 1) // ch

        def push_block(scores):
            full = _exchange(scores)

            def chunk(i, acc):
                s_c = jax.lax.dynamic_slice(sb, (i * ch,), (ch,))
                d_c = jax.lax.dynamic_slice(db, (i * ch,), (ch,))
                return acc + jax.ops.segment_sum(
                    full[s_c], d_c, num_segments=rows + 1
                )

            acc0 = jax.lax.pcast(
                jnp.zeros((rows + 1, scores.shape[1]), jnp.float32),
                "model", to="varying",
            )
            acc = jax.lax.fori_loop(0, n_chunks, chunk, acc0)[:rows]
            return acc * w_l[:, None]

        level_fn = lane_level_xla(
            push_block, row0=row0, rows=rows, w=q * wq, eps_p=eps_p
        )

        return lane_probe_block(
            level_fn, pool_l, plen_l,
            rows=rows, q=q, wq=wq, n_r=n_r,
            max_len=max_len, sqrt_c=sqrt_c, eps_p=eps_p, sentinel=sentinel,
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P("model", None), P("model"),
                  P("model"), P(), P()),
        out_specs=P("model", None),
        # fully manual; inputs and compute replicate over the data axes
        axis_names=set(mesh.axis_names),
    )
    return fn(src_sh, dst_sh, counts, w_full, pool, pool_len)


def _row_pad(sg: ShardedGraph) -> int:
    """Extra score rows so (n_pad + pad) stays mesh-divisible; >= 1 so the
    sentinel row n_pad exists."""
    from repro.models.common import axis_size

    block = max(axis_size("tp"), 1)
    return block - (sg.n_pad % block) if sg.n_pad % block else block


def make_serve_step(cfg, *, queries: int, walk_chunk: int, max_len: int,
                    top_k: int = 50, edge_chunks: int = 8):
    """Build the jit-able ProbeSim serving step for the production mesh.

    step(graph, query_nodes [Q], key) -> (topk_idx [Q, k], topk_val [Q, k])
    One step processes `walk_chunk` walks per query; the serving engine loops
    steps, folding results (estimates are means over walk chunks).
    """
    import math

    sqrt_c = math.sqrt(cfg.c)

    def serve_step(sg: ShardedGraph, query_nodes: Array, key: Array):
        walks = sample_walks_sharded(
            key, sg, query_nodes, walks_per_query=walk_chunk,
            max_len=max_len, sqrt_c=sqrt_c,
        )
        scores = probe_walks_sharded(
            sg, walks, sqrt_c=sqrt_c, edge_chunks=edge_chunks
        )  # [n_pad, Q*B]
        est = scores.reshape(sg.n_pad, queries, walk_chunk).sum(-1) / walk_chunk
        est = constrain(est, "tp", None)
        # exclude the query nodes themselves (compare, not scatter)
        rows = jax.lax.broadcasted_iota(jnp.int32, est.shape, 0)
        est = jnp.where(rows == query_nodes[None, :], -jnp.inf, est)
        vals, idx = jax.lax.top_k(est.T, top_k)  # [Q, k]
        return idx, vals

    return serve_step
