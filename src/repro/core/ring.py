"""Ring-SpMM probe (§Perf hillclimb): shard_map + ppermute pipeline.

The auto-partitioned push (core/distributed.py) re-gathers frontier rows
per edge chunk and pays a full resharding per segment_sum.  The ring variant
makes the exchange explicit: each model shard holds one row block of the
frontier and an edge bucket per (dst_shard=me, src_block); per step it
processes the resident block's bucket and ppermutes the block onward — the
classic 1-D SpMM ring, whose collective volume is exactly ONE frontier pass
per level and whose permutes overlap with the bucket gather/scatter.

Also supports a bf16 frontier (halves the ring traffic; pushes still
accumulate in fp32).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import mesh_axis_names
from repro.utils.pytree import static, struct

Array = jax.Array


@struct
class RingGraph:
    """2-D partitioned edges (partition_edges_2d) + sampling CSR."""

    src_sh: Array  # int32 [S, S, E] src ids relative to their src block
    dst_sh: Array  # int32 [S, S, E] dst ids relative to the dst shard
    in_deg: Array  # int32 [n_pad]
    indptr: Array  # int32 [n_pad]
    indices: Array  # int32 [m_pad]
    n: int = static()
    n_pad: int = static()
    m: int = static()
    shards: int = static()


def build_ring_graph(src: np.ndarray, dst: np.ndarray, n: int, *,
                     shards: int) -> RingGraph:
    from repro.graph.partition import partition_edges_2d

    part = partition_edges_2d(src, dst, n, shards)
    n_pad = part["n_pad"]
    m = len(src)
    m_pad = -(-m // 4096) * 4096  # divisible over every mesh extent
    order = np.argsort(dst, kind="stable")
    indices = np.full(m_pad, n_pad, dtype=np.int32)
    indices[:m] = src[order]
    cnt = np.bincount(dst, minlength=n)
    in_deg = np.zeros(n_pad, dtype=np.int32)
    in_deg[:n] = cnt[:n]
    indptr = np.zeros(n_pad, dtype=np.int32)
    np.cumsum(cnt[: n - 1], out=indptr[1:n])
    return RingGraph(
        src_sh=jnp.asarray(part["src_sh"]),
        dst_sh=jnp.asarray(part["dst_sh"]),
        in_deg=jnp.asarray(in_deg),
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(indices),
        n=n, n_pad=n_pad, m=m, shards=shards,
    )


def ring_graph_abstract(n: int, m: int, shards: int, e_max: int) -> RingGraph:
    """ShapeDtypeStruct RingGraph for the dry-run."""
    from repro.graph.partition import pad_to_multiple

    SDS = jax.ShapeDtypeStruct
    n_pad = pad_to_multiple(n, shards)
    m_pad = -(-m // 4096) * 4096
    return RingGraph(
        src_sh=SDS((shards, shards, e_max), jnp.int32),
        dst_sh=SDS((shards, shards, e_max), jnp.int32),
        in_deg=SDS((n_pad,), jnp.int32),
        indptr=SDS((n_pad,), jnp.int32),
        indices=SDS((m_pad,), jnp.int32),
        n=n, n_pad=n_pad, m=m, shards=shards,
    )


def ring_graph_specs(rg: RingGraph) -> RingGraph:
    tp = "model" if "model" in mesh_axis_names() else None
    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh_axis_names())
    return RingGraph(
        src_sh=P(tp, None, None),
        dst_sh=P(tp, None, None),
        in_deg=P(tp),
        indptr=P(tp),
        indices=P(all_axes if all_axes else None),
        n=rg.n, n_pad=rg.n_pad, m=rg.m, shards=rg.shards,
    )


def _ring_push_level(buf, src_l, dst_l, me, *, shards: int, rows: int,
                     counts_l=None, edge_chunk: int = 2048):
    """One full frontier pass of the double-buffered ring SpMM.

    ``buf`` [rows, C] is this shard's resident frontier block; per step the
    resident block's bucket (dst_shard=me, src_block=blk) is gathered and
    segment-summed into ``acc`` while the block is ppermuted onward — the
    permute overlaps the next bucket's gather/scatter compute.  Returns the
    un-renormalized push accumulator [rows, C] in f32 (callers apply the
    sqrt(c)/in_deg weights).  Shared by the per-level walk probe and the
    lane-batched serve probe.

    With ``counts_l`` (int32 [S], live edges per resident bucket) each
    bucket is walked in ``edge_chunk`` slices with a dynamic trip count, so
    the rectangular [S, S, E] padding costs nothing: live edges are a
    prefix of every bucket and sentinel slots inside the last chunk scatter
    into the dropped segment (their dst is the sentinel ``rows``).  The
    dynamic bound is safe because the ppermute sits OUTSIDE the chunk loop
    — the ring stays in lockstep while skewed buckets finish early.
    """
    C = buf.shape[1]
    acc = jnp.zeros((rows, C), jnp.float32)
    for step in range(shards):
        blk = (me - step) % shards
        src_b = jnp.take(src_l[0], blk, axis=0)  # [E]
        dst_b = jnp.take(dst_l[0], blk, axis=0)
        if counts_l is None:
            bufp = jnp.concatenate(
                [buf, jnp.zeros((1, C), buf.dtype)], axis=0
            )
            msgs = bufp[src_b.clip(0, rows)].astype(jnp.float32)
            acc = acc + jax.ops.segment_sum(
                msgs, dst_b, num_segments=rows + 1
            )[:rows]
        else:
            ch = min(edge_chunk, src_b.shape[0])
            sb = src_b.clip(0, rows - 1)  # sentinel -> garbage row, dropped
            n_chunks = (counts_l[blk] + ch - 1) // ch
            frontier = buf.astype(jnp.float32)

            def chunk(i, a):
                s_c = jax.lax.dynamic_slice(sb, (i * ch,), (ch,))
                d_c = jax.lax.dynamic_slice(dst_b, (i * ch,), (ch,))
                return a + jax.ops.segment_sum(
                    frontier[s_c], d_c, num_segments=rows + 1
                )

            acc0 = jax.lax.pcast(
                jnp.zeros((rows + 1, C), jnp.float32), "model", to="varying"
            )
            acc = acc + jax.lax.fori_loop(0, n_chunks, chunk, acc0)[:rows]
        if step < shards - 1:
            # permute raw bits: XLA's algebraic simplifier otherwise
            # elides the f32->bf16->f32 round-trip and widens the
            # permute back to f32 (2x wire bytes)
            perm = [(i, (i + 1) % shards) for i in range(shards)]
            if buf.dtype == jnp.bfloat16:
                bits = jax.lax.bitcast_convert_type(buf, jnp.uint16)
                bits = jax.lax.ppermute(bits, "model", perm)
                buf = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
            else:
                buf = jax.lax.ppermute(buf, "model", perm)
    return acc


def probe_walks_ring(
    rg: RingGraph,
    walks: Array,  # [C, L] replicated
    *,
    sqrt_c: float,
    eps_p: float = 0.0,
    frontier_dtype=jnp.float32,
) -> Array:
    """Telescoped probe with the ring push; returns scores [n_pad, C]."""
    S = rg.shards
    n_pad = rg.n_pad
    rows = n_pad // S
    C, L = walks.shape
    mesh = jax.sharding.get_abstract_mesh()

    w_full = jnp.where(
        rg.in_deg > 0,
        sqrt_c / jnp.maximum(rg.in_deg.astype(jnp.float32), 1.0),
        0.0,
    )

    def local(walks_l, src_l, dst_l, w_l):
        # walks_l [C_loc, L] (columns sharded over data); src_l/dst_l
        # [1, S, E]; w_l [rows]
        C_loc = walks_l.shape[0]
        me = jax.lax.axis_index("model")
        row0 = me * rows
        scores = jnp.zeros((rows, C_loc), frontier_dtype)

        def rid():
            return jax.lax.broadcasted_iota(jnp.int32, (rows, C_loc), 0) + row0

        for p in range(L, 1, -1):
            scores = scores + (rid() == walks_l[:, p - 1][None, :]).astype(
                scores.dtype
            )
            if eps_p > 0.0:
                thresh = eps_p / (sqrt_c ** (p - 1))
                scores = jnp.where(scores > thresh, scores, 0.0)
            acc = _ring_push_level(scores, src_l, dst_l, me,
                                   shards=S, rows=rows)
            scores = (acc * w_l[:, None]).astype(frontier_dtype)
            scores = jnp.where(rid() == walks_l[:, p - 2][None, :], 0.0, scores)
        return scores

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    col_spec = data_axes if data_axes else None
    manual = {"model"} | set(data_axes)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(col_spec, None), P("model", None, None),
                  P("model", None, None), P("model")),
        out_specs=P("model", col_spec),
        axis_names=manual,
    )
    return fn(walks, rg.src_sh, rg.dst_sh, w_full)


def probe_lanes_ring(
    src_sh: Array,  # int32 [S, S, E] block-relative src ids (sentinel rows)
    dst_sh: Array,  # int32 [S, S, E] block-relative dst ids (sentinel rows)
    w_full: Array,  # f32 [n_pad] sqrt(c)/in_deg renorm weights
    pool: Array,  # int32 [Q*n_r, L] replicated walk pool (sentinel n)
    pool_len: Array,  # int32 [Q*n_r] replicated
    mesh,
    *,
    rows: int,
    shards: int,
    q: int,
    wq: int,
    n_r: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    sentinel: int,
) -> Array:
    """Lane-batched telescoped probe with the ring push; returns [n_pad, W].

    The ring counterpart of ``core.distributed.probe_lanes_sharded``: the
    same compacted lane loop over this shard's frontier block, but each push
    level runs the double-buffered ring exchange (``_ring_push_level``) so
    the collective permute overlaps the per-bucket gather/scatter compute.
    Lane columns replicate over the data axes — the batched program has no
    per-chunk column sharding, so ring serving composes with ANY (Q, n_r)
    instead of falling back on divisibility remainders.
    """
    from repro.core.distributed import lane_level_xla, lane_probe_block

    edge_chunk = 2048
    E = src_sh.shape[2]
    # floor, not width: cap the per-bucket trip count at ~8 so chunking
    # only pays for itself where it skips dead tail slots (same rule as
    # probe_lanes_sharded — tiny chunks re-touch the accumulator)
    ch = min(max(edge_chunk, -(-E // 8)), E)
    e_pad = -(-E // ch) * ch
    if e_pad != E:
        fill = jnp.full(src_sh.shape[:2] + (e_pad - E,), rows, jnp.int32)
        src_sh = jnp.concatenate([src_sh, fill], axis=2)
        dst_sh = jnp.concatenate([dst_sh, fill], axis=2)

    def local(src_l, dst_l, w_l, pool_l, plen_l):
        # src_l/dst_l [1, S, E]; w_l [rows]; pool_l/plen_l replicated
        me = jax.lax.axis_index("model")
        row0 = me * rows
        w = q * wq
        # live edges per resident bucket: sentinel slots (src == rows) are
        # a suffix of every bucket by construction (partition_edges_2d
        # packs each bucket's live prefix first)
        counts_l = (src_l[0] != rows).sum(axis=1).astype(jnp.int32)  # [S]

        def push_block(scores):
            acc = _ring_push_level(scores, src_l, dst_l, me,
                                   shards=shards, rows=rows,
                                   counts_l=counts_l, edge_chunk=ch)
            return acc * w_l[:, None]

        level_fn = lane_level_xla(
            push_block, row0=row0, rows=rows, w=w, eps_p=eps_p
        )

        return lane_probe_block(
            level_fn, pool_l, plen_l,
            rows=rows, q=q, wq=wq, n_r=n_r,
            max_len=max_len, sqrt_c=sqrt_c, eps_p=eps_p, sentinel=sentinel,
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None),
                  P("model"), P(), P()),
        out_specs=P("model", None),
        # fully manual, like the epoch apply step and the spmd lane probe
        axis_names=set(mesh.axis_names),
    )
    return fn(src_sh, dst_sh, w_full, pool, pool_len)


def make_ring_serve_step(cfg, *, queries: int, walk_chunk: int, max_len: int,
                         top_k: int = 50,
                         frontier_dtype=jnp.float32):
    import math

    from repro.core.distributed import sample_walks_sharded

    sqrt_c = math.sqrt(cfg.c)

    def serve_step(rg: RingGraph, query_nodes: Array, key: Array):
        # reuse the CSR sampler via a duck-typed view
        class _V:
            n_pad = rg.n_pad
            in_deg = rg.in_deg
            indptr = rg.indptr
            indices = rg.indices

        walks = sample_walks_sharded(
            key, _V, query_nodes, walks_per_query=walk_chunk,
            max_len=max_len, sqrt_c=sqrt_c,
        )
        scores = probe_walks_ring(
            rg, walks, sqrt_c=sqrt_c, frontier_dtype=frontier_dtype
        )
        est = scores.reshape(rg.n_pad, queries, walk_chunk).sum(-1) / walk_chunk
        rows = jax.lax.broadcasted_iota(jnp.int32, est.shape, 0)
        est = jnp.where(rows == query_nodes[None, :], -jnp.inf, est)
        vals, idx = jax.lax.top_k(est.T, top_k)
        return idx, vals

    return serve_step
