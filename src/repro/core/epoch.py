"""The fused update->query *epoch* pipeline, backend-agnostic (DESIGN.md §5).

ProbeSim's index-free claim means a query is exact against whatever the
graph is NOW — so the natural serving unit on a dynamic graph is an
*epoch*: ONE compiled dispatch that applies an update batch to the
device-resident graph state and serves a query batch against the
just-written buffers, with zero host transfers in between.

PR 2/3 implemented that for the single-device mirror pair only (a
donated-buffer jit private to the session); this module promotes the
epoch to a first-class pipeline over *pluggable stages*

    (graph_state, update_batch, query_batch) -> (graph_state', scores)

so every execution backend composes the same two stages:

* **apply stage** — ``graph_state, UpdateBatch -> graph_state', applied``
  with the coordinated-mirror contracts of ``graph/dynamic.py`` (per-op
  applied mask, sticky overflow, stable delete compaction, version +1
  per changed batch — version/overflow bookkeeping lives with the state
  owner, outside the compiled step where noted);
* **probe stage** — ``graph_state', (keys, us) -> estimates`` running the
  telescoped probe against the post-update buffers.

Two concrete instantiations live here:

* :func:`epoch_step` — the LOCAL epoch: ``apply_update_batch`` composed
  with ``fused_serve_impl`` in one jit with the mirror buffers donated.
  This is the PR-3 session step moved verbatim (same trace, same
  donation, bit-identical results under shared keys);
* :func:`make_sharded_epoch_step` — the MESH epoch over a
  :class:`ShardEpochGraph`: destination-sharded COO buffers + a
  row-sharded ELL table, updated *inside a shard_map step* (each shard
  applies its re-partitioned ops to its own device-resident buffers,
  donation per shard) and probed by the distributed telescoped push in
  the same compiled program.  ``repro.api.backend.ShardedBackend``
  drives it and keeps its host bookkeeping in sync by replaying the
  applied mask.

Layering: this is a *core* module — it knows graph structs, the update
batch format and the probes, but nothing about sessions, specs or
backends (those live in ``repro.api`` and call down into here).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.multisource import fused_serve_impl
from repro.graph.dynamic import UpdateBatch, apply_update_batch
from repro.graph.partition import pad_to_multiple
from repro.graph.structs import EllGraph
from repro.utils.jaxcompat import specs_to_shardings
from repro.utils.pytree import static, struct

Array = jax.Array


# ---------------------------------------------------------------------------
# The pipeline composer
# ---------------------------------------------------------------------------


def epoch_pipeline(apply_stage, probe_stage):
    """Compose an apply stage and a probe stage into one traceable epoch.

    ``apply_stage(graph_state, batch) -> (graph_state', applied)`` and
    ``probe_stage(graph_state', query_batch) -> outputs`` are plain
    traceable callables; the composed function is what a backend jits
    (with its own donation/sharding policy).  ``probe_stage`` may be
    ``None`` for update-only epochs.
    """

    def run(graph_state, batch: UpdateBatch, query_batch=None):
        graph_state, applied = apply_stage(graph_state, batch)
        if probe_stage is None or query_batch is None:
            return graph_state, applied, None
        return graph_state, applied, probe_stage(graph_state, query_batch)

    return run


# ---------------------------------------------------------------------------
# Local epoch step (single-device, donated mirror buffers)
# ---------------------------------------------------------------------------


@partial(
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
    # g/eg are donated so the update scan writes the graph buffers in place
    # (backends that support donation) instead of copying capacity-sized
    # arrays every epoch — the owning backend always replaces its mirrors
    # with the returned g'/eg', and the session own-copies at construction
    # so no caller shares the donated buffers
    donate_argnames=("acc", "g", "eg"),
)
def epoch_step(
    g,
    eg,
    batch: UpdateBatch,
    keys: Array,  # [Q] typed PRNG keys, one stream per query
    us: Array,  # int32 [Q]
    acc: Array,  # f32 [Q, n] donated accumulator
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
    """One fused LOCAL epoch: apply the update batch, serve the query batch.

    The local instantiation of the pipeline: ``apply_update_batch`` writes
    the new COO/ELL buffers and ``fused_serve_impl`` reads them in the same
    compiled program — no host round-trip in between.  Returns
    ``(g', eg', applied, est, idx, vals)`` (``idx``/``vals`` are None when
    ``top_k == 0``); ``g'.version`` / ``g'.overflow`` carry the snapshot id
    and capacity signal.
    """

    def probe(state, qb):
        g2, eg2 = state
        keys_b, us_b, acc_b = qb
        return fused_serve_impl(
            keys_b, g2, eg2, us_b, acc_b, jnp.int32(us_b.shape[0]),
            n_r=n_r,
            lanes_q=lanes_q,
            max_len=max_len,
            sqrt_c=sqrt_c,
            eps_p=eps_p,
            eps_t=eps_t,
            truncation_shift=truncation_shift,
            top_k=top_k,
        )

    run = epoch_pipeline(
        lambda state, b: _pair_apply(state, b), probe
    )
    (g2, eg2), applied, out = run((g, eg), batch, (keys, us, acc))
    acc, est, idx, vals = out[:4]  # the step's counters are not reported here
    return g2, eg2, applied, est, idx, vals


def _pair_apply(state, batch: UpdateBatch):
    g, eg = state
    g2, eg2, applied = apply_update_batch(g, eg, batch)
    return (g2, eg2), applied


# ---------------------------------------------------------------------------
# Sharded epoch graph — device-resident dst-partitioned COO + ELL mirrors
# ---------------------------------------------------------------------------


@struct
class ShardEpochGraph:
    """Device-resident graph state for the mesh epoch.

    The same coordinated mirror pair as the local ``(Graph, EllGraph)``,
    laid out for a ``("data", "model")`` mesh:

    * ``src_sh``/``dst_sh`` int32 [S, E] — per-shard COO buffers holding
      GLOBAL node ids, destination-partitioned (shard s owns every edge
      with ``dst // rows == s``), per-shard FIFO order, sentinel padding
      ``n_pad``.  Flattened they are exactly the COO push operand of the
      distributed telescoped probe;
    * ``counts`` int32 [S] — live edges per shard;
    * ``in_nbrs`` int32 [n_pad, k_max] — the ELL in-neighbor table,
      row-sharded over ``model`` (a shard owns the rows of its node
      block).  Sentinel ``n`` — the LOCAL ELL convention — so the walk
      sampler (``core.walks.sample_walks_batch``) consumes a sliced view
      directly and draws bit-identical walks to the local mirror under
      shared keys;
    * ``in_deg`` int32 [n_pad] — replicated (it is the probe's
      renormalization operand and the sampler's degree table; [n_pad]
      int32 is cheap).

    Updates preserve the invariant that the buffers are bit-identical to
    :func:`build_shard_epoch_graph` rebuilt from the equivalently-updated
    shard-major host edge list (stable FIFO compaction + append-in-stream
    -order, per shard) — the mesh analogue of ``apply_update_batch``'s
    rebuild equality, and what makes carried device state testable
    against a from-scratch rebuild.
    """

    src_sh: Array  # int32 [S, E] global src ids (sentinel n_pad)
    dst_sh: Array  # int32 [S, E] global dst ids (sentinel n_pad)
    counts: Array  # int32 [S]
    in_nbrs: Array  # int32 [n_pad, k_max] (sentinel n)
    in_deg: Array  # int32 [n_pad]
    n: int = static()
    n_pad: int = static()
    rows: int = static()
    shards: int = static()
    capacity: int = static()  # E, per shard
    k_max: int = static()


def build_shard_epoch_graph(
    src: np.ndarray,
    dst: np.ndarray,
    n: int,
    *,
    shards: int,
    capacity_per_shard: int,
    k_max: int,
    mesh=None,
) -> ShardEpochGraph:
    """Build the device epoch state from a shard-major host edge list.

    ``(src, dst)`` must be in shard-major per-shard-FIFO order (what
    ``ShardedGraphState.to_host_edges`` produces — re-partitioning that
    order is the identity, so incremental maintenance and this builder
    agree bit-for-bit).  ``k_max`` caps ELL rows; the max in-degree must
    fit.  With ``mesh`` every buffer is placed straight into its
    :func:`shard_epoch_specs` sharding (each device receives only its own
    rows); without it the buffers land on the default device.
    """
    src = np.asarray(src, np.int32).reshape(-1)
    dst = np.asarray(dst, np.int32).reshape(-1)
    n_pad = pad_to_multiple(n, shards)
    rows = n_pad // shards
    E = int(capacity_per_shard)
    shard_of = dst // rows
    counts = np.bincount(shard_of, minlength=shards).astype(np.int32)
    if counts.max(initial=0) > E:
        raise ValueError(
            f"shard holds {int(counts.max())} edges > capacity {E}"
        )
    src_sh = np.full((shards, E), n_pad, dtype=np.int32)
    dst_sh = np.full((shards, E), n_pad, dtype=np.int32)
    order = np.argsort(shard_of, kind="stable")  # FIFO within shard
    src_o, dst_o = src[order], dst[order]
    starts = np.zeros(shards + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for s in range(shards):
        lo, hi = starts[s], starts[s + 1]
        src_sh[s, : hi - lo] = src_o[lo:hi]
        dst_sh[s, : hi - lo] = dst_o[lo:hi]
    in_deg = np.bincount(dst, minlength=n_pad).astype(np.int32)[:n_pad]
    deg_cap = int(in_deg.max()) if in_deg.size else 0
    if deg_cap > k_max:
        raise ValueError(f"max in-degree {deg_cap} exceeds k_max {k_max}")
    # ELL rows in per-dst stream order — identical to the local
    # ``ell_from_edges`` rows, because shard-major reordering never
    # permutes two edges of the SAME destination
    table = np.full((n_pad, k_max), n, dtype=np.int32)
    d_order = np.argsort(dst, kind="stable")
    d_sorted = dst[d_order]
    s_sorted = src[d_order]
    group_start = np.searchsorted(d_sorted, np.arange(n))
    idx_within = np.arange(len(d_sorted)) - group_start[d_sorted]
    table[d_sorted, idx_within] = s_sorted
    st = ShardEpochGraph(
        src_sh=src_sh, dst_sh=dst_sh, counts=counts, in_nbrs=table,
        in_deg=in_deg,
        n=int(n), n_pad=int(n_pad), rows=int(rows), shards=int(shards),
        capacity=E, k_max=int(k_max),
    )
    if mesh is None:
        return jax.tree.map(jnp.asarray, st)
    return jax.device_put(
        st, specs_to_shardings(shard_epoch_specs(st), mesh=mesh)
    )


def shard_epoch_specs(st: ShardEpochGraph) -> ShardEpochGraph:
    """PartitionSpec pytree for :class:`ShardEpochGraph` (statics copied)."""
    return ShardEpochGraph(
        src_sh=P("model", None),
        dst_sh=P("model", None),
        counts=P("model"),
        in_nbrs=P("model", None),
        in_deg=P(None),  # replicated: probe renorm operand (see class doc)
        n=st.n, n_pad=st.n_pad, rows=st.rows, shards=st.shards,
        capacity=st.capacity, k_max=st.k_max,
    )


# ---------------------------------------------------------------------------
# Sharded apply stage — the shard_map update step
# ---------------------------------------------------------------------------


def _shard_apply(st: ShardEpochGraph, batch: UpdateBatch, mesh):
    """Apply a mixed batch to the per-shard device buffers, in shard_map.

    Each model shard applies the ops whose destination lands in its row
    block, against its OWN buffers — the device-side analogue of
    re-partitioning the batch with ``partition_ops_by_dst`` and applying
    per shard, with ``apply_update_batch``'s exact semantics: deletes
    match the pre-batch buffers (at most one live copy per (s, d) pair
    per batch) and are removed by stable compaction; inserts append in
    stream order iff there is room in BOTH the shard's COO buffer and
    the destination's ELL row.  Returns
    ``(st', applied [B] bool, overflow bool)`` — ``applied`` is the
    OR-fold of the per-shard masks (each op belongs to exactly one
    shard), ``overflow`` is the fresh per-batch capacity signal (the
    sticky fold and the version bump are the state owner's bookkeeping,
    host-side).
    """
    n, n_pad, rows = st.n, st.n_pad, st.rows
    S, E, k_max = st.shards, st.capacity, st.k_max
    has_deletes = batch.has_deletes

    def local(src_b, dst_b, cnt, ell, ideg, bsrc, bdst, bins):
        # src_b/dst_b [1, E]; cnt [1]; ell [rows, k_max]; ideg [n_pad]
        # (replicated, read-only); bsrc/bdst/bins [B] (replicated)
        me = jax.lax.axis_index("model")
        sb, db = src_b[0], dst_b[0]
        valid = (bsrc >= 0) & (bsrc < n) & (bdst >= 0) & (bdst < n)
        mine = valid & (bdst // rows == me)
        d_c = jnp.where(mine, bdst, 0)
        d_loc = jnp.where(mine, bdst - me * rows, 0)
        tri = jnp.tril(jnp.ones((bsrc.shape[0],) * 2, jnp.int32), k=-1)

        if has_deletes:
            is_del = mine & ~bins
            same_pair = (
                (bsrc[None, :] == bsrc[:, None])
                & (bdst[None, :] == bdst[:, None])
                & is_del[None, :]
            )
            del_live = is_del & (
                (same_pair.astype(jnp.int32) * tri).sum(1) == 0
            )
            hits = (
                (sb[None, :] == bsrc[:, None])
                & (db[None, :] == bdst[:, None])
                & del_live[:, None]
            )
            found = hits.any(axis=1)
            pos = jnp.argmax(hits, axis=1)
            del_mask = (
                jnp.zeros(E, bool)
                .at[jnp.where(found, pos, E)]
                .set(True, mode="drop")
            )
            keep = (sb < n_pad) & ~del_mask
            kint = keep.astype(jnp.int32)
            kpos = jnp.cumsum(kint) - kint  # stable compaction
            csrc = (
                jnp.full(E, n_pad, jnp.int32)
                .at[jnp.where(keep, kpos, E)]
                .set(sb, mode="drop")
            )
            cdst = (
                jnp.full(E, n_pad, jnp.int32)
                .at[jnp.where(keep, kpos, E)]
                .set(db, mode="drop")
            )
            cnt2 = kint.sum()
            # ELL mirror: mark deleted slots, stable-compact each touched
            # row once (first op per row rewrites it)
            rows_g = ell[d_loc]  # [B, k_max] pre-batch rows
            s_c = jnp.where(mine, bsrc, n)
            rhit = (rows_g == s_c[:, None]) & found[:, None]
            rfound = rhit.any(axis=1)
            kslot = jnp.argmax(rhit, axis=1)
            dmask = (
                jnp.zeros((rows, k_max), bool)
                .at[jnp.where(rfound, d_loc, rows),
                    jnp.where(rfound, kslot, 0)]
                .set(True, mode="drop")
            )
            same_row = (bdst[None, :] == bdst[:, None]) & rfound[None, :]
            urow = rfound & ((same_row.astype(jnp.int32) * tri).sum(1) == 0)
            live_r = (rows_g < n) & ~dmask[d_loc]
            lint = live_r.astype(jnp.int32)
            new_slot = jnp.cumsum(lint, axis=1) - lint
            b_rows = jnp.broadcast_to(
                jnp.arange(live_r.shape[0])[:, None], live_r.shape
            )
            comp = (
                jnp.full_like(rows_g, n)
                .at[b_rows, jnp.where(live_r, new_slot, k_max)]
                .set(rows_g, mode="drop")
            )
            ell = ell.at[jnp.where(urow, d_loc, rows)].set(comp, mode="drop")
            # post-delete in-degrees, local working copy (each shard only
            # reads entries of its own destinations)
            ideg_w = ideg.at[jnp.where(found, d_c, n_pad)].add(
                -1, mode="drop"
            )
        else:
            found = jnp.zeros_like(valid)
            csrc, cdst, cnt2 = sb, db, cnt[0]
            ideg_w = ideg

        # inserts: append in stream order, coordinated COO+ELL room check
        is_ins = mine & bins
        same_d = (bdst[None, :] == bdst[:, None]) & is_ins[None, :]
        occ = (same_d.astype(jnp.int32) * tri).sum(1)
        slot = ideg_w[d_c] + occ
        ok_ell = is_ins & (slot < k_max)
        oint = ok_ell.astype(jnp.int32)
        cpos = cnt2 + jnp.cumsum(oint) - oint
        ok = ok_ell & (cpos < E)
        csrc = csrc.at[jnp.where(ok, cpos, E)].set(bsrc, mode="drop")
        cdst = cdst.at[jnp.where(ok, cpos, E)].set(bdst, mode="drop")
        ell = ell.at[
            jnp.where(ok, d_loc, rows), jnp.where(ok, slot, k_max)
        ].set(jnp.where(mine, bsrc, n), mode="drop")
        cnt3 = (cnt2 + ok.sum()).astype(jnp.int32)
        ovf = (is_ins & ~ok).any()
        applied = jnp.where(bins, ok, found)
        return (
            csrc[None], cdst[None], cnt3[None], ell,
            applied[None], ovf[None],
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("model", None), P("model", None), P("model"),
            P("model", None), P(), P(), P(), P(),
        ),
        out_specs=(
            P("model", None), P("model", None), P("model"),
            P("model", None), P("model", None), P("model"),
        ),
        # fully manual: inputs and compute are replicated over the data
        # axes, so every data shard produces identical output tiles
        axis_names=set(mesh.axis_names),
    )
    src2, dst2, cnt2, ell2, applied_sh, ovf_sh = fn(
        st.src_sh, st.dst_sh, st.counts, st.in_nbrs, st.in_deg,
        jnp.asarray(batch.src, jnp.int32),
        jnp.asarray(batch.dst, jnp.int32),
        batch.insert,
    )
    applied = applied_sh.any(axis=0)  # ops land on exactly one shard
    overflow = ovf_sh.any()
    # in_deg is replicated (the probe's renorm operand): fold the applied
    # deltas back in the auto region rather than diverging per shard
    ins = jnp.asarray(batch.insert)
    dst_b = jnp.asarray(batch.dst, jnp.int32)
    ideg = st.in_deg.at[
        jnp.where(applied & ~ins, dst_b, st.n_pad)
    ].add(-1, mode="drop")
    ideg = ideg.at[
        jnp.where(applied & ins, dst_b, st.n_pad)
    ].add(1, mode="drop")
    st2 = st.replace(
        src_sh=src2, dst_sh=dst2, counts=cnt2, in_nbrs=ell2, in_deg=ideg
    )
    return st2, applied, overflow


# ---------------------------------------------------------------------------
# Sharded epoch step factory — apply + sample + distributed probe, one jit
# ---------------------------------------------------------------------------


def make_sharded_epoch_step(
    st: ShardEpochGraph,
    mesh,
    *,
    q: int,
    n_r: int,
    top_k: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    eps_t: float,
    truncation_shift: bool,
    walk_chunk: int,
    edge_chunks: int,
    has_deletes: bool,
):
    """Compile the mesh epoch step for one (geometry, Q, n_r, k) config.

    ``step(state, batch, us [Q], keys [Q]) ->
    (state', applied [B], overflow, est, idx, vals)`` — update application
    (shard_map, donated per-shard buffers), walk sampling off the updated
    ELL mirror (bit-identical draws to the local sampler under shared
    keys), and the distributed telescoped probe over the updated COO
    shards all trace into ONE compiled program: no host transfer between
    update and query.  ``q == 0`` (``us``/``keys`` None) compiles the
    update-only variant.  Pass ``has_deletes`` matching the batches this
    step will see (it is part of the jit cache key via the static
    ``UpdateBatch`` field anyway; passing it here keeps the factory's
    cache keys honest).

    The probe marches per-query column chunks of ``walk_chunk`` walks
    through ``probe_walks_sharded`` under ``lax.scan`` (bounded frontier
    memory at large ``n_r``); padding columns are sentinel walks that
    contribute exact zeros.  Epilogue (1/n_r, truncation shift, diagonal
    fix, top-k) matches ``fused_serve_impl``'s conventions, so
    local-vs-sharded epoch parity under shared keys is tolerance-bounded
    by float summation order alone.
    """
    from repro.core.distributed import probe_walks_sharded
    from repro.core.walks import sample_walks_batch

    n, n_pad = st.n, st.n_pad
    S, E = st.shards, st.capacity
    if (S * E) % edge_chunks:
        raise ValueError(
            f"per-shard capacity {E} x {S} shards must divide "
            f"edge_chunks={edge_chunks} (pad capacity up)"
        )
    cc = max(1, min(walk_chunk, n_r)) if q else 1
    n_chunks = -(-n_r // cc) if q else 0
    n_r_pad = n_chunks * cc

    def apply_stage(state, batch):
        state2, applied, overflow = _shard_apply(state, batch, mesh)
        return state2, (applied, overflow)

    def probe_stage(state2, qb):
        us, keys = qb
        # the sampler consumes the updated ELL mirror through a plain
        # EllGraph view — same function, same table rows, same draws as
        # the local epoch under shared keys
        eg_view = EllGraph(
            in_nbrs=state2.in_nbrs[:n],
            in_deg=state2.in_deg[:n],
            n=n, k_max=st.k_max,
        )
        pool = sample_walks_batch(
            keys, eg_view, us, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
        )  # [Q, n_r, L]
        if n_r_pad != n_r:
            pool = jnp.concatenate(
                [pool,
                 jnp.full((q, n_r_pad - n_r, max_len), n, jnp.int32)],
                axis=1,
            )  # sentinel walks: exact-zero columns
        chunks = pool.reshape(q * n_chunks, cc, max_len)
        # probe view: the flattened per-shard COO buffers ARE the push
        # operand (sentinel n_pad edges gather/scatter into zeroed pad
        # rows); indptr/indices are sampler-only fields, unused here
        from repro.core.distributed import ShardedGraph

        sgv = ShardedGraph(
            indptr=state2.in_deg,
            in_deg=state2.in_deg,
            indices=state2.in_deg,
            src=state2.src_sh.reshape(S * E),
            dst=state2.dst_sh.reshape(S * E),
            n=n, n_pad=n_pad, m=S * E, m_pad=S * E,
        )

        def probe_chunk(carry, wchunk):
            scores = probe_walks_sharded(
                sgv, wchunk, sqrt_c=sqrt_c, eps_p=eps_p,
                edge_chunks=edge_chunks,
            )  # [n_pad, cc]
            return carry, scores.sum(axis=1)

        _, sums = jax.lax.scan(probe_chunk, 0, chunks)  # [Q*n_chunks, n_pad]
        counts = sums.reshape(q, n_chunks, n_pad).sum(axis=1)[:, :n]
        est = counts / n_r
        if truncation_shift:
            est = jnp.where(est > 0, est + eps_t / 2, est)
        est = est.at[jnp.arange(q), us].set(1.0)
        if top_k > 0:
            masked = est.at[jnp.arange(q), us].set(-jnp.inf)
            vals, idx = jax.lax.top_k(masked, top_k)
            return est, idx, vals
        return est, None, None

    run = epoch_pipeline(apply_stage, probe_stage if q else None)
    state_shardings = specs_to_shardings(shard_epoch_specs(st), mesh=mesh)

    def step(state, batch, us=None, keys=None):
        state2, (applied, overflow), out = run(
            state, batch, (us, keys) if q else None
        )
        # the carried state leaves with the placement it came in with, so
        # the next epoch's in_shardings accept it
        state2 = jax.lax.with_sharding_constraint(state2, state_shardings)
        if out is None:
            return state2, applied, overflow, None, None, None
        est, idx, vals = out
        return state2, applied, overflow, est, idx, vals

    specs = shard_epoch_specs(st)
    in_specs = (specs, P(), P(), P()) if q else (specs, P())
    return jax.jit(
        step,
        in_shardings=specs_to_shardings(in_specs, mesh=mesh),
        donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# Sharded serve step factory — lane-batched distributed serving, one jit
# ---------------------------------------------------------------------------


def make_sharded_serve_step(
    st: ShardEpochGraph,
    mesh,
    *,
    q: int,
    n_r: int,
    lanes_q: int,
    top_k: int,
    max_len: int,
    sqrt_c: float,
    eps_p: float,
    eps_t: float,
    truncation_shift: bool,
    probe: str = "spmd",
    frontier_dtype: str = "float32",
):
    """Compile the mesh SERVE step for one (geometry, Q, n_r, k) config.

    ``step(state, us [Q], keys [Q]) -> (est, idx, vals)`` (ring:
    ``step(state, ring_src, ring_dst, us, keys)``) — pooled walk sampling
    for the whole query batch off the carried :class:`ShardEpochGraph`'s
    ELL mirror (bit-identical draws to the local sampler under shared
    keys), the compacted telescoped lane probe inside shard_map
    (``probe_lanes_sharded`` / ``probe_lanes_ring``), and the per-query
    reduction + epilogue + top-k, all in ONE compiled program with zero
    host transfers mid-query.  The state is NOT donated: serving reuses
    the resident mirror across calls (``ShardedBackend`` keys it on the
    host mutation counter).

    Epilogue conventions match ``fused_serve_impl`` exactly, and the lane
    schedule is the shared ``core.multisource`` bookkeeping — a batched
    sharded serve therefore equals Q single-query sharded serves bitwise
    (same ``lanes_q``) and matches the local path to float-summation
    tolerance.

    ``frontier_dtype="bfloat16"`` (spmd only) halves the per-level
    all_gather wire volume; parity vs fp32 is ~1e-3 on estimates.
    """
    from repro.core.distributed import probe_lanes_sharded
    from repro.core.walks import sample_walks_batch

    if probe not in ("spmd", "ring"):
        raise ValueError(f"probe must be 'spmd' or 'ring', got {probe!r}")
    n, n_pad, rows, S = st.n, st.n_pad, st.rows, st.shards
    wq = lanes_q

    def serve(state, ring_src, ring_dst, us, keys):
        eg_view = EllGraph(
            in_nbrs=state.in_nbrs[:n],
            in_deg=state.in_deg[:n],
            n=n, k_max=st.k_max,
        )
        pool = sample_walks_batch(
            keys, eg_view, us, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
        ).reshape(q * n_r, max_len)
        pool_len = (pool < n).sum(axis=1).astype(jnp.int32)
        d = state.in_deg.astype(jnp.float32)
        w_full = jnp.where(d > 0, sqrt_c / jnp.maximum(d, 1.0), 0.0)
        if probe == "ring":
            from repro.core.ring import probe_lanes_ring

            total = probe_lanes_ring(
                ring_src, ring_dst, w_full, pool, pool_len, mesh,
                rows=rows, shards=S, q=q, wq=wq, n_r=n_r,
                max_len=max_len, sqrt_c=sqrt_c, eps_p=eps_p, sentinel=n,
            )
        else:
            total = probe_lanes_sharded(
                state.src_sh, state.dst_sh, state.counts, w_full,
                pool, pool_len, mesh,
                n_pad=n_pad, rows=rows, q=q, wq=wq, n_r=n_r,
                max_len=max_len, sqrt_c=sqrt_c, eps_p=eps_p, sentinel=n,
                frontier_dtype=frontier_dtype,
            )  # [n_pad, W]
        acc = total[:n].reshape(n, q, wq).sum(axis=2).T  # [Q, n]
        est = acc / n_r
        if truncation_shift:
            est = jnp.where(est > 0, est + eps_t / 2, est)
        est = est.at[jnp.arange(q), us].set(1.0)
        if top_k > 0:
            masked = est.at[jnp.arange(q), us].set(-jnp.inf)
            vals, idx = jax.lax.top_k(masked, top_k)
            return est, idx, vals
        return est, None, None

    specs = shard_epoch_specs(st)
    if probe == "ring":
        in_specs = (
            specs, P("model", None, None), P("model", None, None), P(), P(),
        )
        return jax.jit(
            serve, in_shardings=specs_to_shardings(in_specs, mesh=mesh)
        )
    in_specs = (specs, P(), P())

    def serve_spmd(state, us, keys):
        return serve(state, None, None, us, keys)

    return jax.jit(
        serve_spmd, in_shardings=specs_to_shardings(in_specs, mesh=mesh)
    )
