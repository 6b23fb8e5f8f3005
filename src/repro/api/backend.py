"""Execution backends under :class:`~repro.api.session.SimRankSession`.

PR 3 unified the query/update surface into one session, but the session
could only *execute* one way: the single-device fused path.  The
distributed substrate (``core/distributed.py``'s auto-partitioned probe,
``core/ring.py``'s shard_map ring, ``graph/partition.py``) was a dead
island no user-facing API could reach.  This module is the bridge: a
``Backend`` protocol the session dispatches through, with two
implementations —

* :class:`LocalBackend` — the extraction of the session's original
  dispatch paths (``single_source``/``topk``/``multi_source*`` plus the
  coordinated :class:`GraphHandle` update path).  Bit-identical to the
  pre-backend session under shared keys: same core entry points, same
  pow-2 update bucketing, same compiled shapes.
* :class:`ShardedBackend` — the same ``QuerySpec -> ResultEnvelope``
  contract over a device mesh: destination-partitioned edge shards
  (:func:`repro.graph.partition.partition_edges_by_dst` bookkeeping via
  :class:`ShardedGraphState`), the distributed walk sampler + telescoped
  probe (``probe='spmd'``, the auto-partitioned baseline) or the
  shard_map ring push (``probe='ring'``), and dynamic updates applied
  shard-wise with the same version/overflow semantics as
  ``GraphHandle.apply_batch``.

The session stays the owner of everything *around* execution — specs,
PRNG streams, queues/tickets, stats, envelopes, the §4.4 planner — and
asks the backend only to (a) serve a batch, (b) apply an update
sub-batch, (c) recover capacity, (d) report snapshot state.  Both
backends batch differently behind that one surface: the local backend
fuses queries across lane columns of one compiled step; the sharded
backend loops ring walk-chunks over the mesh and folds partial counts on
host.

Randomness: both backends honor per-query PRNG streams.  The sharded
backend derives chunk keys as ``fold_in(stream, chunk_index)``, so its
answers are deterministic per (stream, graph snapshot) and independent
of batch composition — the same contract the local path tests pin —
but its draws are *different* draws than the local sampler's (different
walk-table layout), so cross-backend parity is tolerance-based, not
bit-identical (tests/test_backend.py).
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp
from repro.api.handle import GraphHandle
from repro.api.spec import QuerySpec
from repro.core.epoch import (
    build_shard_epoch_graph,
    epoch_step,
    make_sharded_epoch_step,
    make_sharded_serve_step,
)
from repro.core.multisource import multi_source, multi_source_topk
from repro.core.params import ProbeSimParams
from repro.core.probesim import single_source, topk
from repro.graph.dynamic import (
    UpdateBatch,
    apply_update_batch_jit,
    make_update_batch,
)
from repro.graph.partition import pad_to_multiple, partition_ops_by_dst
from repro.utils.jaxcompat import make_mesh
from repro.utils.spans import DISPATCH_FETCH, span

Array = jax.Array


def _hub_nodes_from_degrees(deg: np.ndarray, percentile: float) -> frozenset:
    """Nodes at or above the ``percentile``-th in-degree among positive
    degrees — the hub set the accuracy controller's probe cache targets
    (PRSim's power-law analysis: a few heavy hitters absorb most query
    traffic on skewed graphs, so their probe rows are worth sharing)."""
    if not 0.0 <= percentile <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {percentile}")
    deg = np.asarray(deg)
    pos = deg[deg > 0]
    if pos.size == 0:
        return frozenset()
    thr = max(float(np.percentile(pos, percentile)), 1.0)
    return frozenset(int(u) for u in np.flatnonzero(deg >= thr))


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class Backend(Protocol):
    """What the session needs from an execution substrate.

    Implementations own the graph state (device mirrors or sharded
    buffers) and the compiled serve steps; the session owns specs, PRNG
    streams, queues, stats and envelopes.  ``serve_batch`` is the one
    required query entry point (``serve_one`` has a default route through
    it on both shipped backends) and returns ``(est, idx, vals,
    levels)`` on the host, ``levels`` the probe levels the dispatch ran
    or None where the backend does not count them; its ``live`` says how
    many leading slots are real queries (the rest repeat-pad the batch),
    which a backend may use to give the live queries every lane column;
    updates arrive as
    homogeneous sub-batches (one ``insert`` flag per call, duplicate
    delete pairs already split by the session) and return a per-op
    applied mask with ``GraphHandle.apply_batch`` semantics: an
    unapplied insert means capacity overflow (sticky ``overflow``,
    recover via ``regrow``), an unapplied delete means the edge was
    absent.

    Backends that set ``supports_epoch`` additionally implement the fused
    epoch stage (``core.epoch``): ``epoch_batch`` applies one padded
    ``UpdateBatch`` and serves one query batch in a single compiled
    dispatch (zero host transfers in between) and ``own_buffers`` makes
    the backend's graph state exclusively owned (deep copy) — the session
    calls it at construction so donated epoch steps can never invalidate
    caller-held buffers.
    """

    name: str
    supports_epoch: bool
    variants: tuple[str, ...]
    # the push the latest fused serve dispatch's probe levels ran
    # (``core.multisource.push_path``), or None where it is not reported
    push_path: str | None
    # the lane columns each slot of that dispatch owned, or None
    lanes: list[int] | None
    # the walks that dispatch's pool materialized, or None
    walks_sampled: int | None

    @property
    def n(self) -> int: ...

    @property
    def version(self) -> int: ...

    @property
    def overflow(self) -> bool: ...

    def host_in_degrees(self) -> np.ndarray: ...

    def hub_nodes(self, percentile: float) -> frozenset: ...

    def dispatch_label(self, variant: str) -> str: ...

    def batch_dispatch_label(self, q: int) -> str: ...

    def epoch_dispatch_label(self) -> str: ...

    def serve_one(
        self, spec: QuerySpec, key, *, variant: str, n_r: int
    ) -> dict: ...

    def serve_batch(
        self, kind: str, us, keys, *, key=None, k: int = 0, n_r: int,
        live: int | None = None,
    ) -> tuple: ...

    def apply_ops(
        self, src: np.ndarray, dst: np.ndarray, insert: bool
    ) -> np.ndarray: ...

    def regrow(self, **kwargs) -> None: ...

    def to_host_edges(self) -> tuple[np.ndarray, np.ndarray]: ...

    def own_buffers(self) -> None: ...

    def epoch_batch(
        self,
        batch: UpdateBatch,
        us,
        keys,
        *,
        n_r: int,
        top_k: int,
        lanes: int | None = None,
    ) -> tuple: ...


# ---------------------------------------------------------------------------
# Local backend — the extracted single-device dispatch paths
# ---------------------------------------------------------------------------


class LocalBackend:
    """Single-device execution over an owned :class:`GraphHandle`.

    This is PR 3's session dispatch verbatim, moved behind the protocol:
    one-shot specs delegate to the core entry points (so an explicit
    ``spec.key`` reproduces the legacy calls bit-for-bit), batched specs
    run the fused multi-query step, updates go through the coordinated
    both-mirrors path with pow-2 bucketed batches.  The handle is shared
    with the session (``session.handle is backend.handle``), which keeps
    the fused epoch path — which donates and replaces the mirror buffers
    in place — working unchanged.
    """

    name = "local"
    supports_epoch = True
    variants = ("auto", "telescoped", "tree", "reference", "randomized")
    push_path: str | None = None
    lanes: list[int] | None = None
    walks_sampled: int | None = None

    def __init__(
        self,
        handle: GraphHandle,
        *,
        params: ProbeSimParams,
        walk_chunk: int = 256,
    ):
        if not isinstance(handle, GraphHandle):
            raise TypeError("LocalBackend takes a GraphHandle")
        self.handle = handle
        self.params = params
        self.walk_chunk = walk_chunk
        self._hubs: tuple | None = None  # ((version, percentile), frozenset)

    # -- snapshot state ------------------------------------------------------

    @property
    def n(self) -> int:
        return self.handle.n

    @property
    def version(self) -> int:
        return self.handle.version

    @property
    def overflow(self) -> bool:
        return self.handle.overflow

    def host_in_degrees(self) -> np.ndarray:
        return np.asarray(self.handle.eg.in_deg)

    def hub_nodes(self, percentile: float) -> frozenset:
        """High in-degree hub set, cached per (graph version, percentile)."""
        ck = (self.version, float(percentile))
        if self._hubs is None or self._hubs[0] != ck:
            self._hubs = (
                ck, _hub_nodes_from_degrees(self.host_in_degrees(), percentile)
            )
        return self._hubs[1]

    def dispatch_label(self, variant: str) -> str:
        """Envelope ``variant`` field: the legacy variant, verbatim."""
        return variant

    def batch_dispatch_label(self, q: int) -> str:
        """The fused local step serving a Q-query burst, lane count
        annotated (mirrors ``ShardedBackend.batch_dispatch_label``)."""
        return f"local[fused,Q={int(q)}]"

    def epoch_dispatch_label(self) -> str:
        """Envelope ``variant`` for epoch results (the fused local path)."""
        return "telescoped"

    def to_host_edges(self) -> tuple[np.ndarray, np.ndarray]:
        return self.handle.to_host_edges()

    # -- queries -------------------------------------------------------------

    def serve_one(self, spec: QuerySpec, key, *, variant: str, n_r: int) -> dict:
        """One single-node spec via the legacy entry points (bit-identical
        to ``single_source``/``topk`` under the same key)."""
        g, eg = self.handle.g, self.handle.eg
        p = (
            self.params
            if n_r == self.params.n_r
            else dataclasses.replace(self.params, n_r=n_r)
        )
        if spec.kind == "single_source":
            est = single_source(
                key, g, eg, spec.node, p, variant=variant,
                walk_chunk=self.walk_chunk,
            )
            return dict(scores=np.asarray(est))
        idx, vals = topk(
            key, g, eg, spec.node, spec.k, p, variant=variant,
            walk_chunk=self.walk_chunk,
        )
        return dict(topk_nodes=np.asarray(idx), topk_scores=np.asarray(vals))

    def serve_batch(
        self, kind: str, us, keys, *, key=None, k: int = 0, n_r: int,
        live: int | None = None,
    ) -> tuple:
        """One fused multi-query dispatch; returns ``(est, idx, vals,
        levels)`` (est for single_source kind, idx/vals for topk — the
        unused side is None; ``levels`` is the probe levels the step ran)
        and sets ``push_path``, ``lanes`` and ``walks_sampled``.
        Exactly one of ``keys`` ([Q] per-query streams) / ``key`` (scalar:
        legacy split semantics) is set.  ``live`` (None: every slot)
        leading slots are real queries and own all the lane columns; the
        padding slots' rows carry no estimate."""
        g, eg = self.handle.g, self.handle.eg
        us = jnp.asarray(us, jnp.int32)
        info: dict = {}
        common = dict(
            lanes=self.walk_chunk, n_r=n_r, keys=keys, info=info, live=live
        )
        if kind == "topk":
            est = None
            idx, vals = multi_source_topk(
                key, g, eg, us, k, self.params, **common
            )
        else:
            idx = vals = None
            est = multi_source(key, g, eg, us, self.params, **common)
        with span(DISPATCH_FETCH):  # one transfer, answers and counts
            (est, idx, vals), (levels, lanes, walks) = jax.device_get((
                (est, idx, vals),
                (info["levels"], info["lanes"], info["walks_sampled"]),
            ))
        self.push_path, self.lanes = info["push_path"], lanes.tolist()
        self.walks_sampled = int(walks)
        return est, idx, vals, int(levels)

    # -- updates -------------------------------------------------------------

    def apply_ops(
        self, src: np.ndarray, dst: np.ndarray, insert: bool
    ) -> np.ndarray:
        """Apply one homogeneous sub-batch through the coordinated
        both-mirrors path; pow-2 padded so variable-size bursts reuse a
        log-bounded set of compiled shapes."""
        bucket = 1 << (int(src.shape[0]) - 1).bit_length()
        batch = make_update_batch(
            src, dst, insert, batch_size=bucket, n=self.handle.n
        )
        return np.asarray(self.handle.apply_batch(batch))[: src.shape[0]]

    def regrow(self, **kwargs) -> None:
        self.handle.regrow(**kwargs)

    # -- fused epochs --------------------------------------------------------

    def own_buffers(self) -> None:
        """Deep-copy the handle so donated epoch steps touch no caller arrays."""
        self.handle = self.handle.copy()

    def epoch_batch(
        self,
        batch: UpdateBatch,
        us,
        keys,
        *,
        n_r: int,
        top_k: int,
        lanes: int | None = None,
    ) -> tuple:
        """One fused local epoch: ``core.epoch.epoch_step`` over the owned
        mirrors (donated; the handle is replaced with the post-epoch
        snapshot).  ``us=None`` runs the update-only variant.  Returns
        ``(applied [B], est, idx, vals)`` as host arrays (est for
        ``top_k == 0``, idx/vals otherwise; the unused side is None).
        """
        h = self.handle
        if us is None:
            g2, eg2, applied = apply_update_batch_jit(h.g, h.eg, batch)
            h.g, h.eg = g2, eg2
            return np.asarray(applied), None, None, None
        p = self.params
        q = len(us)
        acc = jnp.zeros((q, h.n), jnp.float32)
        g2, eg2, applied, est, idx, vals = epoch_step(
            h.g, h.eg, batch, keys, jnp.asarray(us, jnp.int32), acc,
            n_r=n_r,
            lanes_q=max(1, (lanes or self.walk_chunk) // q),
            max_len=p.max_len,
            sqrt_c=p.sqrt_c,
            eps_p=p.eps_p,
            eps_t=p.eps_t,
            truncation_shift=p.truncation_shift,
            top_k=top_k,
        )
        if top_k:
            idx = np.asarray(idx)  # device sync (materializes g2/eg2)
            vals = np.asarray(vals)
            est = None
        else:
            est = np.asarray(est)
            idx = vals = None
        h.g, h.eg = g2, eg2
        return np.asarray(applied), est, idx, vals


# ---------------------------------------------------------------------------
# Sharded graph state — dst-partitioned host buffers + device mirrors
# ---------------------------------------------------------------------------


class ShardedGraphState:
    """Destination-partitioned edge state with GraphHandle-style dynamics.

    The authoritative copy is a pair of host buffers ``[S, E]`` (global
    src/dst ids, per-shard FIFO order, ``counts[s]`` live entries each) —
    exactly the layout :func:`partition_edges_by_dst` produces, plus
    capacity headroom.  Updates are applied *shard-wise*: an incoming
    batch is re-partitioned by destination shard (``dst // rows``) and
    each shard appends/deletes in its own buffer.  Semantics mirror
    ``GraphHandle.apply_batch``:

    * an insert applies iff its shard has room; a skipped insert sets the
      sticky ``overflow`` flag and is reported unapplied (never dropped);
    * a delete removes at most one live copy of its (src, dst) pair per
      *batch* — exactly ``apply_update_batch``'s contract; the session's
      occurrence split feeds duplicate pairs in separate batches — with
      stable compaction (FIFO order preserved) and a per-op found mask;
    * ``version`` advances by exactly one per batch that changed the
      graph; ``regrow`` doubles per-shard capacity, clears ``overflow``
      and preserves ``version`` (a representation change, not a graph
      change).

    Device mirrors (:class:`~repro.core.distributed.ShardedGraph`, and a
    :class:`~repro.core.ring.RingGraph` for the ring probe) are built
    lazily from the host buffers and invalidated on every applied batch;
    because partitioning is deterministic and per-shard order is FIFO,
    the incremental mirrors are bit-identical to rebuilding from
    :meth:`to_host_edges` — the invariant tests/test_backend.py pins.
    """

    def __init__(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        n: int,
        *,
        shards: int,
        capacity_per_shard: int | None = None,
        version: int = 0,
    ):
        src = np.asarray(src, np.int32).reshape(-1)
        dst = np.asarray(dst, np.int32).reshape(-1)
        self.n = int(n)
        self.shards = int(shards)
        self.n_pad = pad_to_multiple(self.n, self.shards)
        self.rows = self.n_pad // self.shards
        shard_of = dst // self.rows
        counts = np.bincount(shard_of, minlength=self.shards).astype(np.int64)
        e_cap = int(capacity_per_shard or 0)
        e_cap = max(e_cap, int(counts.max()) if len(src) else 1, 1)
        self._src_sh = np.full((self.shards, e_cap), -1, dtype=np.int32)
        self._dst_sh = np.full((self.shards, e_cap), -1, dtype=np.int32)
        self._counts = counts
        order = np.argsort(shard_of, kind="stable")  # FIFO within shard
        src_o, dst_o = src[order], dst[order]
        starts = np.zeros(self.shards + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        for s in range(self.shards):
            lo, hi = starts[s], starts[s + 1]
            self._src_sh[s, : hi - lo] = src_o[lo:hi]
            self._dst_sh[s, : hi - lo] = dst_o[lo:hi]
        self.version = int(version)
        self.overflow = False
        self._device = None  # (ShardedGraph, RingGraph | None) cache
        # bumped on every buffer/geometry mutation; the epoch path keys
        # its carried device mirror on it (stale counter => rebuild)
        self.mutations = 0

    # -- snapshot ------------------------------------------------------------

    @property
    def capacity_per_shard(self) -> int:
        return self._src_sh.shape[1]

    @property
    def num_edges(self) -> int:
        return int(self._counts.sum())

    def to_host_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """Live edges, shard-major with per-shard FIFO order.

        This order is the fixpoint of the partitioner: re-partitioning it
        reproduces the exact per-shard sequences, so a state rebuilt from
        ``to_host_edges()`` has bit-identical device mirrors.
        """
        src = np.concatenate(
            [self._src_sh[s, : self._counts[s]] for s in range(self.shards)]
        )
        dst = np.concatenate(
            [self._dst_sh[s, : self._counts[s]] for s in range(self.shards)]
        )
        return src, dst

    def host_in_degrees(self) -> np.ndarray:
        _, dst = self.to_host_edges()
        return np.bincount(dst, minlength=self.n)[: self.n]

    def copy(self) -> "ShardedGraphState":
        """Deep copy (buffers nobody else references).

        ``to_host_edges`` is shard-major per-shard-FIFO, the fixpoint of
        the partitioner, so the copy's buffers are bit-identical.
        """
        st = ShardedGraphState(
            *self.to_host_edges(), self.n,
            shards=self.shards,
            capacity_per_shard=self.capacity_per_shard,
            version=self.version,
        )
        st.overflow = self.overflow
        return st

    # -- shard-wise updates --------------------------------------------------

    def apply_ops(
        self, src: np.ndarray, dst: np.ndarray, insert: bool
    ) -> np.ndarray:
        """Apply one re-partitioned homogeneous batch; per-op applied mask."""
        src = np.asarray(src, np.int32).reshape(-1)
        dst = np.asarray(dst, np.int32).reshape(-1)
        applied = np.zeros(src.shape[0], dtype=bool)
        if src.shape[0] == 0:
            return applied
        shard_of, touched = partition_ops_by_dst(
            dst, self.n_pad, self.shards
        )
        for s in touched:
            idx = np.where(shard_of == s)[0]
            if insert:
                free = self.capacity_per_shard - int(self._counts[s])
                take = idx[:free]
                c = int(self._counts[s])
                self._src_sh[s, c : c + len(take)] = src[take]
                self._dst_sh[s, c : c + len(take)] = dst[take]
                self._counts[s] += len(take)
                applied[take] = True
                if len(take) < len(idx):
                    self.overflow = True  # sticky; skipped ops stay unapplied
            else:
                # vectorized first-match delete (same ``apply_batch``
                # batch semantics: at most ONE live copy removed per
                # (src, dst) pair per batch — the session's occurrence
                # split feeds duplicate pairs in separate batches).
                # Stable argsort + searchsorted finds each pair's
                # earliest (FIFO) live slot in one pass instead of an
                # O(ops x live) python scan.
                c = int(self._counts[s])
                live_s = self._src_sh[s, :c]
                live_d = self._dst_sh[s, :c]
                base = np.int64(self.n + 1)
                live_keys = live_s.astype(np.int64) * base + live_d
                op_keys = src[idx].astype(np.int64) * base + dst[idx]
                first_of_pair = np.zeros(len(idx), dtype=bool)
                first_of_pair[np.unique(op_keys, return_index=True)[1]] = True
                order = np.argsort(live_keys, kind="stable")
                pos = np.searchsorted(live_keys[order], op_keys)
                cand = np.where(first_of_pair & (pos < c))[0]
                hit = cand[live_keys[order[pos[cand]]] == op_keys[cand]]
                if len(hit):
                    kill = np.zeros(c, dtype=bool)
                    kill[order[pos[hit]]] = True
                    applied[idx[hit]] = True
                    keep = ~kill  # stable compaction: FIFO order preserved
                    nk = int(keep.sum())
                    self._src_sh[s, :nk] = live_s[keep]
                    self._dst_sh[s, :nk] = live_d[keep]
                    self._src_sh[s, nk:c] = -1
                    self._dst_sh[s, nk:c] = -1
                    self._counts[s] = nk
        if applied.any():
            self.version += 1  # once per batch that changed the graph
            self._device = None
            self.mutations += 1
        return applied

    def replay_applied(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        insert: np.ndarray,
        applied: np.ndarray,
    ) -> None:
        """Mirror a device-applied epoch batch into the host buffers.

        The mesh epoch step applies updates on device
        (``core.epoch._shard_apply``); this replays its per-op decisions —
        applied deletes first (first live FIFO match per op), then applied
        inserts (append in stream order) — so the host buffers stay
        bit-identical to the carried device state without re-deriving the
        room checks.  ``version`` advances once iff anything applied; the
        caller folds the device overflow flag into the sticky host flag.
        """
        src = np.asarray(src).astype(np.int64, copy=False)
        dst = np.asarray(dst).astype(np.int64, copy=False)
        insert = np.asarray(insert, bool)
        applied = np.asarray(applied, bool)
        if not applied.any():
            return
        for i in np.where(applied & ~insert)[0]:
            s, d = int(src[i]), int(dst[i])
            sh = d // self.rows
            c = int(self._counts[sh])
            hit = np.where(
                (self._src_sh[sh, :c] == s) & (self._dst_sh[sh, :c] == d)
            )[0]
            if not len(hit):  # device said applied: the edge was live
                raise RuntimeError(
                    f"epoch replay: delete ({s}, {d}) not found on host "
                    f"shard {sh} — device/host state diverged"
                )
            j = int(hit[0])
            self._src_sh[sh, j : c - 1] = self._src_sh[sh, j + 1 : c].copy()
            self._dst_sh[sh, j : c - 1] = self._dst_sh[sh, j + 1 : c].copy()
            self._src_sh[sh, c - 1] = -1
            self._dst_sh[sh, c - 1] = -1
            self._counts[sh] -= 1
        for i in np.where(applied & insert)[0]:
            s, d = int(src[i]), int(dst[i])
            sh = d // self.rows
            c = int(self._counts[sh])
            if c >= self.capacity_per_shard:
                raise RuntimeError(
                    f"epoch replay: shard {sh} full on host but the device "
                    "applied an insert — device/host state diverged"
                )
            self._src_sh[sh, c] = s
            self._dst_sh[sh, c] = d
            self._counts[sh] += 1
        self.version += 1
        self._device = None
        self.mutations += 1

    def ensure_capacity(self, capacity_per_shard: int) -> None:
        """Grow per-shard buffers to at least ``capacity_per_shard``.

        Unlike :meth:`regrow` this is pure headroom bookkeeping: it never
        clears ``overflow`` and never touches ``version`` (the epoch path
        uses it to round capacity up to the probe's edge-chunk multiple).
        """
        new_cap = int(capacity_per_shard)
        if new_cap <= self.capacity_per_shard:
            return
        grown_s = np.full((self.shards, new_cap), -1, dtype=np.int32)
        grown_d = np.full((self.shards, new_cap), -1, dtype=np.int32)
        grown_s[:, : self.capacity_per_shard] = self._src_sh
        grown_d[:, : self.capacity_per_shard] = self._dst_sh
        self._src_sh, self._dst_sh = grown_s, grown_d
        self._device = None
        self.mutations += 1

    def regrow(self, *, capacity_per_shard: int | None = None,
               growth: float = 2.0) -> None:
        """Double (or set) per-shard capacity; clears ``overflow``,
        preserves ``version`` and the per-shard FIFO order."""
        new_cap = int(
            capacity_per_shard
            or max(int(self.capacity_per_shard * growth),
                   self.capacity_per_shard + 1)
        )
        if new_cap > self.capacity_per_shard:
            self.ensure_capacity(new_cap)
        self.overflow = False

    # -- device mirrors ------------------------------------------------------

    def device_graphs(self, *, edge_chunks: int, want_ring: bool):
        """The device-resident mirrors, rebuilt lazily after updates."""
        if self._device is None:
            from repro.core.distributed import build_sharded_graph

            src, dst = self.to_host_edges()
            dcount = max(len(jax.devices()), 1)
            # generous edge padding + m normalized to m_pad: the compiled
            # serve steps key on the device mirror's static metadata, so
            # update batches that stay within one padded capacity band
            # reuse the same executable instead of recompiling per edge
            sg = build_sharded_graph(
                src, dst, self.n,
                pad_nodes=self.shards,
                # the band floor must stay divisible by edge_chunks or
                # _push_chunked's reshape assertion fires
                pad_edges=max(edge_chunks * dcount,
                              pad_to_multiple(1024, edge_chunks)),
            )
            sg = sg.replace(m=sg.m_pad)
            rg = None
            if want_ring:
                rg = self._build_ring(src, dst)
            self._device = (sg, rg)
        elif want_ring and self._device[1] is None:
            src, dst = self.to_host_edges()
            self._device = (self._device[0], self._build_ring(src, dst))
        return self._device

    def _build_ring(self, src: np.ndarray, dst: np.ndarray):
        from repro.core.ring import build_ring_graph

        rg = build_ring_graph(src, dst, self.n, shards=self.shards)
        # m normalized to the padded indices length for the same
        # compiled-step-reuse reason as the ShardedGraph mirror above
        return rg.replace(m=int(rg.indices.shape[0]))


# ---------------------------------------------------------------------------
# Sharded backend — mesh execution behind the same contract
# ---------------------------------------------------------------------------


class ShardedBackend:
    """Mesh-sharded execution: dst-partitioned graph, distributed probe.

    Construct from a :class:`GraphHandle` (``GraphHandle.shard`` does
    exactly this) or an existing :class:`ShardedGraphState`.  ``shards``
    is the row-partition count = the mesh's ``model`` extent; the mesh
    defaults to ``(n_devices // shards, shards)`` over ``("data",
    "model")`` — walk columns shard over ``data``, frontier rows over
    ``model`` (the core/distributed.py layout).

    Serving is *lane-batched*: one compiled step per (Q, n_r, k) samples
    the whole batch's walk pool off the carried device-resident
    :class:`~repro.core.epoch.ShardEpochGraph` (the epoch path's mirror,
    keyed on the host mutation counter — repeated ``drain()`` serving
    reuses resident device state), runs the compacted telescoped lane
    probe inside shard_map — all-gather push (``probe='spmd'``) or the
    double-buffered ring exchange (``probe='ring'``) — and reduces
    per-query counts + top-k in the same program.  Zero host transfers
    mid-query; each query owns ``walk_chunk // Q`` lane columns (the
    local fused path's full-batch schedule, shared via
    ``core.multisource``; repeat-padding slots own theirs too, where the
    local path deals every column to the live queries).
    The epilogue (1/n_r, truncation shift, diagonal fix, top-k) matches
    the local path's conventions so results are tolerance-comparable.

    The fused update->query epoch runs on the mesh too
    (``supports_epoch=True``): ``epoch_batch`` drives
    ``core.epoch.make_sharded_epoch_step`` — a carried device-resident
    :class:`~repro.core.epoch.ShardEpochGraph` (dst-sharded COO buffers +
    row-sharded ELL mirror) is updated inside a shard_map step and probed
    by the distributed telescoped push in the same compiled program, with
    no host transfer between update and query.  The host
    ``ShardedGraphState`` stays authoritative by replaying the applied
    mask (``replay_applied``) after each epoch; any host-path mutation
    (``apply_ops``/``regrow``) invalidates the carried mirror, which is
    rebuilt from host on the next epoch — bit-identical to the carried
    state by the stable-FIFO invariant.
    """

    name = "sharded"
    push_path = None  # the mesh probes keep their own pushes
    lanes = None  # and their own lane schedule
    walks_sampled = None  # and their own walk pools
    supports_epoch = True
    variants = ("auto", "telescoped")

    def __init__(
        self,
        state: ShardedGraphState | GraphHandle,
        *,
        params: ProbeSimParams,
        shards: int | None = None,
        mesh=None,
        walk_chunk: int = 128,
        probe: str = "spmd",
        edge_chunks: int = 4,
        capacity_per_shard: int | None = None,
        frontier_dtype: str = "float32",
    ):
        if probe not in ("spmd", "ring"):
            raise ValueError(f"probe must be 'spmd' or 'ring', got {probe!r}")
        if frontier_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"frontier_dtype must be 'float32' or 'bfloat16', "
                f"got {frontier_dtype!r}"
            )
        if isinstance(state, GraphHandle):
            state = state.shard(
                shards=shards, mesh=mesh,
                capacity_per_shard=capacity_per_shard,
            )
        if shards is not None and shards != state.shards:
            raise ValueError(
                f"shards={shards} != state partitioned into {state.shards}"
            )
        self.state = state
        self.params = params
        self.walk_chunk = int(walk_chunk)
        self.probe = probe
        self.edge_chunks = int(edge_chunks)
        self.frontier_dtype = frontier_dtype
        if mesh is None:
            ndev = len(jax.devices())
            s = state.shards
            if ndev % s:
                raise ValueError(
                    f"{s} shards need a device count divisible by {s}; "
                    f"have {ndev} (pass an explicit mesh= to override)"
                )
            mesh = make_mesh((ndev // s, s), ("data", "model"))
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"ShardedBackend needs a mesh with a 'model' axis (frontier "
                f"rows shard over it); got axes {tuple(mesh.axis_names)}"
            )
        if mesh.shape["model"] != state.shards:
            raise ValueError(
                f"mesh model extent {mesh.shape['model']} != "
                f"shards {state.shards}"
            )
        self.mesh = mesh
        self._steps: dict = {}  # serve config -> compiled batched step
        # the carried device-resident epoch mirror (ShardEpochGraph) and
        # the host-state mutation counter it was last synced against
        self._epoch_graph = None
        self._epoch_sync = -1
        self._epoch_steps: dict = {}  # config -> compiled epoch step
        self._hubs: tuple | None = None  # ((version, percentile), frozenset)

    # -- snapshot state ------------------------------------------------------

    @property
    def n(self) -> int:
        return self.state.n

    @property
    def version(self) -> int:
        return self.state.version

    @property
    def overflow(self) -> bool:
        return self.state.overflow

    def host_in_degrees(self) -> np.ndarray:
        return self.state.host_in_degrees()

    def hub_nodes(self, percentile: float) -> frozenset:
        """High in-degree hub set, cached per (graph version, percentile)."""
        ck = (self.version, float(percentile))
        if self._hubs is None or self._hubs[0] != ck:
            self._hubs = (
                ck, _hub_nodes_from_degrees(self.host_in_degrees(), percentile)
            )
        return self._hubs[1]

    def dispatch_label(self, variant: str) -> str:
        """Envelope ``variant`` field: records the mesh path that served."""
        return f"sharded[{self.probe}]"

    def batch_dispatch_label(self, q: int) -> str:
        """The dispatch label annotated with the batch lane count — names
        the compiled step that serves a Q-query burst (one executable per
        (Q, n_r, k, probe, capacity band))."""
        return f"sharded[{self.probe},Q={int(q)}]"

    def epoch_dispatch_label(self) -> str:
        """Epoch envelopes record the path that actually served: the mesh
        epoch always telescopes through the spmd push (the ring layout's
        2-D edge buckets have no incremental maintenance yet — ROADMAP),
        so a ``probe="ring"`` backend must not stamp ring on epochs."""
        return "sharded[spmd]"

    def to_host_edges(self) -> tuple[np.ndarray, np.ndarray]:
        return self.state.to_host_edges()

    # -- updates (shard-wise) ------------------------------------------------

    def apply_ops(
        self, src: np.ndarray, dst: np.ndarray, insert: bool
    ) -> np.ndarray:
        return self.state.apply_ops(src, dst, insert)

    def regrow(self, **kwargs) -> None:
        # map GraphHandle.regrow's kwargs onto per-shard capacity; k_max
        # has no ELL analogue here and capacity is per shard already
        kwargs.pop("k_max", None)
        cap = kwargs.pop("capacity", None)
        if cap is not None and "capacity_per_shard" not in kwargs:
            kwargs["capacity_per_shard"] = pad_to_multiple(
                int(cap), self.state.shards
            ) // self.state.shards
        if "capacity_per_shard" in kwargs:
            # an explicit total is split evenly; on a skewed dst
            # distribution that split can undershoot the hot shard's
            # current buffer — clamp so regrow always makes progress
            # (never clear the overflow flag without adding room)
            kwargs["capacity_per_shard"] = max(
                int(kwargs["capacity_per_shard"]),
                self.state.capacity_per_shard + 1,
            )
        self.state.regrow(**kwargs)

    # -- fused epochs (device-resident shard buffers) ------------------------

    def own_buffers(self) -> None:
        """Deep-copy the graph state so epochs never mutate caller buffers."""
        self.state = self.state.copy()
        self._epoch_graph = None
        self._epoch_sync = -1

    def _epoch_graph_state(self):
        """The carried device epoch mirror, rebuilt when host state moved.

        Rebuild sizes the per-shard capacity up to the probe's edge-chunk
        multiple (growing the host buffers to match, so device and host
        room checks agree) and the ELL width to the current max in-degree
        plus headroom — an ELL-full insert therefore reports unapplied,
        sets overflow, and the session's regrow/retry loop makes progress
        on the rebuilt (wider) mirror.
        """
        if (
            self._epoch_graph is not None
            and self._epoch_sync == self.state.mutations
        ):
            return self._epoch_graph
        E = pad_to_multiple(
            max(self.state.capacity_per_shard, self.edge_chunks),
            self.edge_chunks,
        )
        self.state.ensure_capacity(E)
        # materialize the edge list ONCE — it feeds both the k_max sizing
        # and the builder (to_host_edges is an O(m) concatenation)
        src, dst = self.state.to_host_edges()
        deg_cap = (
            int(np.bincount(dst, minlength=self.state.n).max())
            if len(dst) else 0
        )
        st = build_shard_epoch_graph(
            src, dst, self.state.n,
            shards=self.state.shards,
            capacity_per_shard=self.state.capacity_per_shard,
            k_max=max(deg_cap + 8, 16),
            mesh=self.mesh,
        )
        self._epoch_graph = st
        self._epoch_sync = self.state.mutations
        return st

    def epoch_batch(
        self,
        batch: UpdateBatch,
        us,
        keys,
        *,
        n_r: int,
        top_k: int,
        lanes: int | None = None,
    ) -> tuple:
        """One fused MESH epoch: shard_map update apply + distributed probe
        in a single compiled dispatch against the carried device mirror
        (donated per shard; no host transfer between update and query).
        The applied mask is replayed into the host ``ShardedGraphState``
        afterwards, keeping ``to_host_edges``/``version``/serving mirrors
        coherent.  Same return contract as ``LocalBackend.epoch_batch``.
        """
        st = self._epoch_graph_state()
        q = 0 if us is None else len(us)
        cfg = (
            q, n_r if q else 0, top_k if q else 0,
            bool(batch.has_deletes), st.capacity, st.k_max,
        )
        step = self._epoch_steps.get(cfg)
        if step is None:
            p = self.params
            step = make_sharded_epoch_step(
                st, self.mesh,
                q=q, n_r=n_r if q else 1, top_k=top_k,
                max_len=p.max_len, sqrt_c=p.sqrt_c, eps_p=p.eps_p,
                eps_t=p.eps_t, truncation_shift=p.truncation_shift,
                walk_chunk=self.walk_chunk, edge_chunks=self.edge_chunks,
                has_deletes=bool(batch.has_deletes),
            )
            self._epoch_steps[cfg] = step
        # host copies of the op stream BEFORE the dispatch (the replay
        # below must not read donated device buffers)
        b_src = np.asarray(batch.src)
        b_dst = np.asarray(batch.dst)
        b_ins = np.asarray(batch.insert)
        with jax.set_mesh(self.mesh):
            if q:
                out = step(st, batch, jnp.asarray(us, jnp.int32), keys)
            else:
                out = step(st, batch)
        st2, applied, overflow, est, idx, vals = out
        applied = np.asarray(applied)
        self.state.replay_applied(b_src, b_dst, b_ins, applied)
        if bool(np.asarray(overflow)):
            self.state.overflow = True
        self._epoch_graph = st2
        self._epoch_sync = self.state.mutations
        if top_k and q:
            return applied, None, np.asarray(idx), np.asarray(vals)
        if q:
            return applied, np.asarray(est), None, None
        return applied, None, None, None

    # -- queries -------------------------------------------------------------

    def serve_one(self, spec: QuerySpec, key, *, variant: str, n_r: int) -> dict:
        est, idx, vals, _ = self.serve_batch(
            spec.kind, [spec.node], jnp.stack([key]),
            k=spec.k or 0, n_r=n_r,
        )
        if spec.kind == "single_source":
            return dict(scores=est[0])
        return dict(topk_nodes=idx[0], topk_scores=vals[0])

    def serve_batch(
        self, kind: str, us, keys, *, key=None, k: int = 0, n_r: int,
        live: int | None = None,
    ) -> tuple:
        """ONE lane-batched mesh dispatch per query batch.

        Pooled walk sampling for the whole batch, the compacted telescoped
        lane probe inside shard_map, per-query reduction + top-k — all in a
        single compiled step against the carried device-resident
        :class:`~repro.core.epoch.ShardEpochGraph` (the same mirror the
        epoch path carries, keyed on the host mutation counter, so repeated
        ``drain()``/ticket serving reuses resident device state instead of
        rebuilding from host buffers).  Compiled once per
        (Q, k, n_r, probe, capacity band); zero host transfers mid-query.
        Returns ``(est, idx, vals, None)``: the mesh step does not count
        its probe levels.  ``live`` is ignored: every slot owns
        ``walk_chunk // Q`` lane columns, padding included.
        """
        us = np.asarray(us, np.int32).reshape(-1)
        q = us.shape[0]
        if keys is None:
            if key is None:
                raise ValueError("serve_batch needs `key` or per-query `keys`")
            keys = jax.random.split(key, q)  # legacy scalar-key semantics
        st = self._epoch_graph_state()
        wq = max(1, self.walk_chunk // q)
        ring_args = ()
        ring_band = None
        if self.probe == "ring":
            # ring buckets have no incremental maintenance yet (ROADMAP);
            # the mutation-keyed device cache rebuilds them lazily
            _, rg = self.state.device_graphs(
                edge_chunks=self.edge_chunks, want_ring=True
            )
            ring_args = (rg.src_sh, rg.dst_sh)
            ring_band = rg.src_sh.shape
        cfg = (
            q, int(k), int(n_r), wq, self.probe,
            st.capacity, st.k_max, ring_band, self.frontier_dtype,
        )
        step = self._steps.get(cfg)
        if step is None:
            p = self.params
            step = make_sharded_serve_step(
                st, self.mesh,
                q=q, n_r=int(n_r), lanes_q=wq, top_k=int(k),
                max_len=p.max_len, sqrt_c=p.sqrt_c, eps_p=p.eps_p,
                eps_t=p.eps_t, truncation_shift=p.truncation_shift,
                probe=self.probe,
                frontier_dtype=self.frontier_dtype,
            )
            self._steps[cfg] = step
        with jax.set_mesh(self.mesh):
            est, idx, vals = step(
                st, *ring_args, jnp.asarray(us), jnp.asarray(keys)
            )
        if kind == "single_source":
            return np.asarray(est), None, None, None
        return None, np.asarray(idx), np.asarray(vals), None
