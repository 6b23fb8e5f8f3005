"""`SimRankSession` — the single query/update surface over a live graph.

ProbeSim's selling point is that index-free queries and graph updates are
the *same* object: a query runs against whatever the graph is NOW.  The
seed split that story across five query signatures, two engines with
incompatible result types, and a ``(g, eg)`` mirror pair every caller
threaded by hand.  The session unifies all of it:

    h = GraphHandle.from_edges(src, dst, n, capacity=m + 4096, k_max=64)
    sess = SimRankSession(h, eps_a=0.1, top_k=10, batch_q=8)

    env = sess.query(QuerySpec(kind="topk", node=u))     # one-shot
    for u in nodes:
        sess.submit(u)                                   # queued ...
    results = sess.drain(budget_walks=512)               # ... fused batches

    sess.update(inserts=(new_src, new_dst))              # apply NOW
    ep = sess.epoch(inserts=(s, d), queries=[u1, u2])    # fused upd->query

Three dispatch paths, one surface (each preserves its legacy engine's exact
PRNG and shape semantics — the deprecation shims in repro.serving delegate
here and are bit-identical to their pre-session behavior):

* ``query(spec)`` — one-shot, delegates to the core entry points
  (``single_source``/``topk``/``multi_source*``), so a spec with an
  explicit ``key`` is bit-identical to the legacy call under that key;
* ``submit``/``drain`` — the serving path: per-query PRNG streams assigned
  at submit time, fixed-size repeat-padded batches through the fused
  multi-query step (one compiled dispatch per batch); ``submit`` returns
  a :class:`QueryTicket` for async consumption (``poll``/``result``) —
  ``drain`` is the synchronous collect-everything special case;
* ``update``/``epoch`` — updates applied through the coordinated
  both-mirrors path; ``epoch`` fuses one update batch + one query batch
  into a single jitted step with zero host transfers in between, and
  auto-regrows on capacity overflow (nothing is ever silently dropped).

Execution is pluggable (repro.api.backend): the session owns specs, PRNG
streams, queues/tickets, stats and envelopes, and dispatches through a
``Backend`` — ``LocalBackend`` (the single-device fused path above,
bit-identical to the pre-backend session) or ``ShardedBackend`` (the
same contract over a device mesh).  The fused epoch is a Backend stage
too (``core.epoch``): local epochs donate the session-owned mirror pair,
mesh epochs update device-resident shard buffers inside a shard_map step
— both with zero host transfers between update and query.

The §4.4 "best of both worlds" switch lives in the session *planner*
(:meth:`plan`): ``variant='auto'`` picks the deterministic prefix-tree
probe when the walk pool shares prefixes heavily (n_r >> in-degree of the
query node — the host-static analogue of the paper's per-level cost
comparison) and the fused telescoped path otherwise; batched specs always
take the fused path (it is the only batched one).

Every result is a ``ResultEnvelope`` carrying the graph ``version`` it was
computed against, the walk budget actually spent, and the Thm-1/2 error
bound evaluated at that effective budget.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from repro.api.backend import Backend, LocalBackend, ShardedBackend
from repro.api.handle import GraphHandle
from repro.api.spec import QuerySpec, ResultEnvelope, as_spec
from repro.core.accuracy import (
    AccuracyController,
    ProbeCache,
    escalation_schedule,
)
from repro.core.epoch import epoch_step  # noqa: F401  (re-exported: the
#   fused local epoch step now lives in core/epoch.py; legacy importers —
#   serving.dynamic_engine among them — keep finding it here)
from repro.core.params import ProbeSimParams, abs_error_bound, make_params
from repro.graph.dynamic import UpdateBatch, make_update_batch
from repro.utils.spans import DISPATCH, span

Array = jax.Array


@dataclass
class EngineStats:
    """Dispatch counters, threaded through every session path.

    ``queries``/``updates`` count logical work (queries answered, edge ops
    applied); ``steps`` counts fused serve dispatches, ``epochs`` fused
    update->query epochs, ``regrows`` capacity recoveries, ``retries``
    straggler re-dispatches (incremented by serving.straggler callers);
    ``escalations`` counts accuracy-controller rounds beyond the first
    (extra dispatches adaptive queries paid), ``hub_hits`` whole serve
    dispatches skipped because every row of an escalation round was
    already in the hub probe cache.  ``probe_levels`` totals the probe
    levels of the fused serve dispatches whose backend counts them;
    ``push_path`` names the push the latest of them ran
    (``core.multisource.push_path``), ``lanes`` the fewest lane columns
    a live query of it owned (the query that sets its length) and
    ``walks_sampled`` the walks its pool materialized (n_r for each live
    query).
    """

    queries: int = 0
    updates: int = 0
    steps: int = 0
    retries: int = 0
    epochs: int = 0
    regrows: int = 0
    escalations: int = 0
    hub_hits: int = 0
    probe_levels: int = 0
    push_path: str | None = None
    lanes: int | None = None
    walks_sampled: int | None = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclass
class QueryTicket:
    """Async handle for one submitted query.

    ``submit()`` fixes the query's PRNG stream and returns a ticket;
    the answer materializes when a drain/epoch serves the ticket's batch.
    ``poll()`` is the non-blocking check (None while pending); ``result()``
    forces service — it drains queued batches (in submission order, so
    earlier tickets resolve on the way) until this ticket is answered.
    ``drain()`` remains the synchronous serve-everything special case.
    """

    spec: QuerySpec
    seq: int  # session submission sequence number (the PRNG stream id)
    _session: "SimRankSession" = field(repr=False, default=None)
    envelope: ResultEnvelope | None = field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return self.envelope is not None

    def poll(self) -> ResultEnvelope | None:
        """The envelope if this ticket has been served, else None."""
        return self.envelope

    def result(self, *, budget_walks: int | None = None) -> ResultEnvelope:
        """Block until served: runs queued batches up to this ticket."""
        if self.envelope is None:
            self._session._drain_until(self, budget_walks=budget_walks)
        return self.envelope


@dataclass
class UpdateReport:
    """Outcome of one immediate ``update()`` call."""

    submitted: int = 0
    applied: int = 0
    regrows: int = 0
    # overflow-skipped inserts, as (src, dst, True) tuples — only populated
    # when auto_regrow=False (with it, skips are regrown and retried here)
    skipped: list = field(default_factory=list)
    version: int = -1
    overflow: bool = False


@dataclass
class EpochResult:
    """Outcome of one fused update→query epoch."""

    version: int  # graph snapshot id AFTER the update batch
    overflow: bool  # sticky capacity signal (pre-regrow value)
    regrown: bool  # True if auto_regrow ran after this epoch
    updates_submitted: int  # live (non-padding) ops in the batch
    updates_applied: int  # ops that changed the graph
    updates_requeued: int  # overflow-skipped inserts pushed back for retry
    # overflow-skipped inserts this epoch, as (src, dst, True) tuples.  With
    # auto_regrow they are also re-queued (updates_requeued); without, the
    # caller regrows manually and re-submits these — never silently lost
    skipped_ops: list[tuple[int, int, bool]] = field(default_factory=list)
    results: list[ResultEnvelope] = field(default_factory=list)
    latency_s: float = 0.0


def _occurrence_numbers(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """occ[i] = #{j < i : (src[j], dst[j]) == (src[i], dst[i])}, vectorized.

    The np.unique/np.cumsum formulation of the multigraph split: stable-sort
    ops by pair, number each op by its offset from its pair group's start,
    scatter back to stream order.  Replaces the O(Q) python dict loop the
    seed engine used.
    """
    pairs = src.astype(np.int64) * np.int64(n + 1) + dst.astype(np.int64)
    _, inv, counts = np.unique(pairs, return_inverse=True, return_counts=True)
    if counts.max() <= 1:
        return np.zeros(len(pairs), np.int64)
    order = np.argsort(inv, kind="stable")  # stable: stream order per group
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    occ = np.empty(len(pairs), np.int64)
    occ[order] = np.arange(len(pairs)) - np.repeat(starts, counts)
    return occ


class SimRankSession:
    """SimRank serving session over a pluggable execution :class:`Backend`.

    ``backend`` selects the execution substrate behind the one
    ``QuerySpec -> ResultEnvelope`` surface: ``"local"`` (default) is the
    single-device fused path over an owned :class:`GraphHandle` —
    bit-identical to the pre-backend session under shared keys;
    ``"sharded"`` places the graph on a device mesh
    (:class:`repro.api.backend.ShardedBackend`: dst-partitioned shards,
    distributed probe, shard-wise updates; size the mesh with ``shards=``
    / ``mesh=``).  A ready-made :class:`Backend` instance can be passed
    directly as the first argument instead of a handle; if it advertises
    the epoch stage (``supports_epoch``), the session asks it to own-copy
    its graph state at construction so fused epochs stay donation-safe.

    ``walk_chunk`` is the total lane-column width of the fused serve step
    (per-query walk-chunk width on the sharded backend); ``batch_q`` the
    fixed query width of ``drain()``/``epoch()`` batches (short batches
    are repeat-padded so jit compiles one step per shape);
    ``update_batch`` the fixed op width of epoch update batches.
    ``top_k`` is the default k for specs that don't pin one.

    Adaptive accuracy (``core/accuracy.py``): specs with ``epsilon`` set
    escalate geometrically from ``initial_budget`` walks until a
    certificate meets the request; ``confidence`` is the default coverage
    of the empirical CLT certificate; ``hub_percentile`` selects the
    high in-degree hub set whose probe rows are cached and shared across
    queries and drain batches (``probe_cache_entries`` bounds the cache).

    With ``auto_regrow`` (default), capacity overflow triggers host-side
    compaction into 2x buffers and the skipped inserts are retried — no
    update is ever lost; with ``auto_regrow=False`` skips are surfaced in
    the ``UpdateReport``/``EpochResult`` for the caller to handle.

    The session OWNS its graph state (``own_graph=True`` copies the handle
    at construction): the fused epoch step donates the mirror buffers, so
    they must not be shared with the caller.  ``own_graph=False`` skips the
    copy for read-mostly use (queries/updates over a handle the caller
    keeps authoritative) — ``epoch()`` is disabled there, since donation
    would invalidate the caller's buffers.  Randomness: every query gets
    its own PRNG stream — ``fold_in(session_seed, submission_seq)`` — at
    submit/query time, so batch composition never changes an answer
    (docs/api.md, "PRNG-stream determinism contract").

    Thread safety: ``submit``/``drain``/``query``/``update``/``epoch``
    compose under concurrent callers — one re-entrant session lock
    serializes queue mutation, PRNG-stream assignment, ticket fills and
    graph mutation (the HTTP serving front end drives one session from
    handler and collector threads at once).  Dispatches run inside the
    lock, so a long drain blocks concurrent submitters for its duration;
    answers remain determined by each query's submit-time stream alone.
    """

    def __init__(
        self,
        handle: GraphHandle | Backend,
        *,
        c: float = 0.6,
        eps_a: float = 0.1,
        delta: float = 0.01,
        walk_chunk: int = 256,
        top_k: int = 50,
        seed: int = 0,
        batch_q: int = 8,
        update_batch: int = 64,
        auto_regrow: bool = True,
        own_graph: bool = True,
        backend: str | Backend = "local",
        shards: int | None = None,
        mesh=None,
        backend_options: dict | None = None,
        initial_budget: int = 64,
        confidence: float = 0.99,
        hub_percentile: float = 90.0,
        probe_cache_entries: int = 256,
    ):
        if isinstance(handle, (LocalBackend, ShardedBackend)) or (
            not isinstance(handle, GraphHandle) and isinstance(handle, Backend)
        ):
            if backend != "local":  # the untouched default
                raise ValueError(
                    "pass either a Backend instance or backend=..., not both"
                )
            backend, handle = handle, None
        elif not isinstance(handle, GraphHandle):
            raise TypeError(
                "SimRankSession takes a GraphHandle — build one with "
                "GraphHandle.from_edges(src, dst, n)"
            )
        elif not isinstance(backend, str):
            # a GraphHandle positional + a ready Backend instance: the
            # handle would be silently shadowed by the backend's own graph
            raise ValueError(
                "a Backend instance brings its own graph state — pass it "
                "as the first argument instead of a GraphHandle"
            )
        self._plan_deg: tuple[int, np.ndarray] | None = None  # (version, in_deg)
        self.walk_chunk = walk_chunk
        self.top_k = top_k
        self.batch_q = batch_q
        self.update_batch = update_batch
        self.auto_regrow = auto_regrow
        if isinstance(backend, str):
            if backend == "local":
                if shards is not None or mesh is not None or backend_options:
                    # a forgotten backend="sharded" must not silently
                    # build an unsharded session
                    raise ValueError(
                        "shards/mesh/backend_options only apply to "
                        "backend='sharded' — did you forget to set it?"
                    )
                self.handle = handle.copy() if own_graph else handle
                self._owns_graph = own_graph
                self.params = make_params(
                    handle.n, c=c, eps_a=eps_a, delta=delta
                )
                self.backend: Backend = LocalBackend(
                    self.handle, params=self.params,
                    walk_chunk=walk_chunk,
                )
            elif backend == "sharded":
                self.params = make_params(
                    handle.n, c=c, eps_a=eps_a, delta=delta
                )
                self.backend = ShardedBackend(
                    handle, params=self.params, shards=shards, mesh=mesh,
                    walk_chunk=walk_chunk, **(backend_options or {}),
                )
                # the sharded state owns a partitioned copy of the edges;
                # the constructor handle is not kept (it would go stale on
                # the first shard-wise update)
                self.handle = None
                self._owns_graph = True
            else:
                raise ValueError(
                    f"backend must be 'local', 'sharded' or a Backend "
                    f"instance, got {backend!r}"
                )
        else:
            if shards is not None or mesh is not None or backend_options:
                raise ValueError(
                    "shards/mesh/backend_options configure session-built "
                    "backends; a ready Backend instance already carries "
                    "its geometry — construct it with those options"
                )
            self.backend = backend
            # capability detection: a backend advertising the epoch stage
            # (supports_epoch + epoch_batch) gets epochs even though the
            # caller built it — the session asks it to own-copy its graph
            # state NOW, so the donating epoch steps can never invalidate
            # buffers the caller still holds.  Backends without the stage
            # stay read-shared and epoch() refuses.
            if getattr(backend, "supports_epoch", False) and hasattr(
                backend, "own_buffers"
            ):
                backend.own_buffers()
                self._owns_graph = True
            else:
                self._owns_graph = False
            self.handle = getattr(backend, "handle", None)
            # adopt the backend's error-budget accounting when it has one,
            # so envelopes report the bound the executing substrate uses
            self.params = getattr(backend, "params", None) or make_params(
                backend.n, c=c, eps_a=eps_a, delta=delta
            )
        if initial_budget < 1:
            raise ValueError("initial_budget must be >= 1")
        if not 0.0 < confidence < 1.0:
            raise ValueError("confidence must be in (0, 1)")
        self.initial_budget = int(initial_budget)
        self.confidence = float(confidence)
        self.hub_percentile = float(hub_percentile)
        self.key = jax.random.key(seed)
        self.query_queue: deque[tuple[QuerySpec, Array, QueryTicket]] = deque()
        self.update_queue: deque[tuple[int, int, bool]] = deque()
        self.stats = EngineStats()
        self._seq = 0  # submission counter -> per-query PRNG stream
        # hub probe sharing (core/accuracy.py): adaptive queries on hub
        # nodes ride NODE-keyed PRNG streams (a salted fold_in of the
        # session key, not the submit-order stream), which makes their
        # per-round score rows identical across queries and drain batches
        # — the cache then skips whole dispatches when every row of a
        # round is resident.  Session-seed-deterministic like everything
        # else; caller-pinned spec.key bypasses both rekey and cache.
        self._probe_cache = ProbeCache(probe_cache_entries)
        self._hub_root = jax.random.fold_in(self.key, 0x5B5B)
        # one re-entrant lock serializes every path that mutates shared
        # session state — the submission queues, the seq counter behind the
        # PRNG streams, ticket fills, and graph mutation — so concurrent
        # callers (the serving front end's handler + collector threads)
        # compose safely.  Re-entrant because epoch() routes through
        # submit()/queue_update(), and drain() through _serve_next_batch().
        # Dispatches run INSIDE the lock: answers stay batch-composition
        # deterministic and two threads can never double-serve one ticket.
        self._lock = threading.RLock()

    # -- snapshot state ------------------------------------------------------

    @property
    def version(self) -> int:
        """Current graph snapshot id (bumped once per applied update batch)."""
        return self.backend.version

    @property
    def overflow(self) -> bool:
        """Sticky capacity signal (cleared by ``regrow``)."""
        return self.backend.overflow

    @property
    def pending(self) -> tuple[int, int]:
        """(queued update ops, queued queries)."""
        return len(self.update_queue), len(self.query_queue)

    def error_bound(self, n_r: int | None = None) -> float:
        """Thm 1+2 absolute-error bound at the effective walk count."""
        return abs_error_bound(self.params, n=self.backend.n, n_r=n_r)

    def regrow(self, **kwargs) -> None:
        """Manual capacity recovery (see :meth:`GraphHandle.regrow`)."""
        with self._lock:
            self.backend.regrow(**kwargs)
            self.stats.regrows += 1

    def record_retry(self, n: int = 1) -> None:
        """Public hook for dispatch-layer retries (straggler policies).

        ``EngineStats`` is owned by the session/backend pair; external
        dispatch wrappers (``repro.serving.straggler`` callers) report
        their re-dispatches through this method instead of mutating
        ``stats`` fields directly.
        """
        if n < 0:
            raise ValueError(f"retry count must be >= 0, got {n}")
        self.stats.retries += n

    # -- PRNG streams --------------------------------------------------------

    def _query_key(self) -> Array:
        with self._lock:
            k = jax.random.fold_in(self.key, self._seq)
            self._seq += 1
            return k

    # -- planner -------------------------------------------------------------

    def plan(self, spec: QuerySpec) -> str:
        """Resolve ``variant='auto'`` — the §4.4 best-of-both-worlds switch.

        Decided on host from static statistics (TPU control flow must be
        shape-static): batched specs take the fused telescoped path (the
        only batched one); a single query takes the deterministic
        prefix-tree probe when its walk pool must share first-step prefixes
        heavily — n_r >= 8 x in-degree(u), the host analogue of the paper's
        per-level deterministic-vs-randomized cost comparison — and the
        fused telescoped path otherwise.
        """
        if spec.variant != "auto":
            if spec.variant not in self.backend.variants:
                raise ValueError(
                    f"variant {spec.variant!r} is not available on the "
                    f"{self.backend.name!r} backend "
                    f"(supports {self.backend.variants})"
                )
            return spec.variant
        if spec.nodes is not None or "tree" not in self.backend.variants:
            return "telescoped"
        n_r = spec.budget_walks or self.params.n_r
        # host in-degree snapshot, refreshed once per graph version — the
        # planner must not pay a device->host sync per query on the hot path
        if self._plan_deg is None or self._plan_deg[0] != self.version:
            self._plan_deg = (self.version, self.backend.host_in_degrees())
        d = int(self._plan_deg[1][spec.node])
        if d > 0 and n_r >= 8 * d:
            return "tree"
        return "telescoped"

    # -- one-shot queries ----------------------------------------------------

    def query(
        self,
        spec: QuerySpec | int,
        *,
        budget_walks: int | None = None,
        deadline_s: float | None = None,
    ) -> ResultEnvelope:
        """Serve one spec now, bypassing the queue.

        Delegates to the core entry points, so results under an explicit
        ``spec.key`` are bit-identical to the legacy calls: single-node
        specs reproduce ``single_source(key, ...)`` / ``topk(key, ...)``
        (key-split semantics), batched specs ``multi_source(_topk)`` (a
        ``[Q]`` key array is passed through as per-query streams).  With
        ``spec.key=None`` the session assigns its own submit-order streams.

        A spec with ``epsilon`` set runs the adaptive accuracy controller
        instead (``core/accuracy.py``): escalate geometrically from the
        session's ``initial_budget`` until a certificate meets epsilon,
        capped at ``budget_walks`` (or the flat Thm-1 budget).
        ``deadline_s`` clamps escalation (adaptive specs only): a miss
        degrades to the best-so-far answer with ``certificate='deadline'``
        — it never raises.
        """
        spec = as_spec(spec, default_k=self.top_k)
        if budget_walks is not None and spec.budget_walks is None:
            spec = dataclasses.replace(spec, budget_walks=budget_walks)
        if spec.epsilon is not None:
            with self._lock:
                return self._query_adaptive(spec, deadline_s=deadline_s)
        if deadline_s is not None:
            raise ValueError(
                "deadline_s clamps the adaptive escalation loop — it "
                "requires a spec with epsilon set (for flat-budget specs "
                "use serving.straggler.dispatch around query())"
            )
        with self._lock:
            return self._query_flat(spec)

    def _query_flat(self, spec: QuerySpec) -> ResultEnvelope:
        variant = self.plan(spec)
        n_r = spec.budget_walks or self.params.n_r
        levels = None
        if spec.nodes is None:
            key = spec.key if spec.key is not None else self._query_key()
            with span(DISPATCH) as sp:
                out = self.backend.serve_one(
                    spec, key, variant=variant, n_r=n_r
                )
        else:
            if variant != "telescoped":
                raise ValueError(
                    f"batched specs require the fused telescoped path, "
                    f"got variant={variant!r}"
                )
            key, keys = self._multi_keys(spec)
            with span(DISPATCH) as sp:
                est, idx, vals, levels = self.backend.serve_batch(
                    spec.kind, spec.nodes, keys, key=key, k=spec.k or 0,
                    n_r=n_r,
                )
            out = (
                dict(scores=est)
                if spec.kind == "single_source"
                else dict(topk_nodes=idx, topk_scores=vals)
            )
        self.stats.steps += 1
        self.stats.queries += spec.q
        self.stats.probe_levels += levels or 0
        path, lanes, walks = self._note_dispatch(levels, spec.q)
        return ResultEnvelope(
            kind=spec.kind,
            node=spec.node,
            nodes=spec.nodes,
            walks_used=n_r,
            latency_s=sp.seconds,
            version=self.version,
            error_bound=self.error_bound(n_r),
            variant=self.backend.dispatch_label(variant),
            probe_levels=levels,
            push_path=path,
            lanes=None if lanes is None else min(lanes),
            walks_sampled=walks,
            **out,
        )

    def _note_dispatch(self, levels, live: int):
        """``(push_path, lanes, walks_sampled)`` of the fused dispatch
        that just ran: its push, the lane columns each slot owned and the
        walks its pool materialized, kept in ``stats.push_path``,
        ``stats.walks_sampled`` and, as the fewest over the ``live``
        leading slots, ``stats.lanes``; all None where the dispatch
        counted no levels (one-shot variants, the mesh backend)."""
        if levels is None:
            return None, None, None
        path = getattr(self.backend, "push_path", None)
        lanes = getattr(self.backend, "lanes", None)
        walks = getattr(self.backend, "walks_sampled", None)
        self.stats.push_path = path
        self.stats.walks_sampled = walks
        if lanes is not None:
            self.stats.lanes = min(lanes[:live])
        return path, lanes, walks

    def _multi_keys(self, spec: QuerySpec):
        """(key, keys) for a batched spec — exactly one of the two is set."""
        q = spec.q
        if spec.key is None:
            return None, jnp.stack([self._query_key() for _ in range(q)])
        k = spec.key
        if getattr(k, "ndim", 0) == 1:
            if k.shape[0] != q:
                raise ValueError(
                    f"per-query key array has {k.shape[0]} streams "
                    f"for {q} nodes"
                )
            return None, k
        return k, None  # scalar key: legacy split semantics

    # -- adaptive accuracy serving (core/accuracy.py) ------------------------

    def _query_adaptive(
        self, spec: QuerySpec, *, deadline_s: float | None = None
    ) -> ResultEnvelope:
        """One-shot adaptive spec: run the escalation loop now.

        Single-node specs return their per-query envelope directly; a
        batched ``nodes`` spec fans out to per-node items (a scalar
        ``spec.key`` is split into per-query streams — there is no legacy
        adaptive path to reproduce) and collapses to ONE envelope whose
        certificate is the batch's weakest member (``walks_used``/
        ``certified_bound``/``rounds`` are the per-query maxima).
        """
        if spec.nodes is None:
            key = spec.key if spec.key is not None else self._query_key()
            envs = self._serve_adaptive([(spec, key)], deadline_s=deadline_s)
            self.stats.queries += 1
            return envs[0]
        key, keys = self._multi_keys(spec)
        if keys is None:
            keys = jax.random.split(key, spec.q)
        subs = [
            dataclasses.replace(spec, node=int(u), nodes=None)
            for u in spec.nodes
        ]
        envs = self._serve_adaptive(
            list(zip(subs, list(keys))), deadline_s=deadline_s
        )
        self.stats.queries += spec.q
        worst = max(envs, key=lambda e: e.certified_bound)
        walks = max(e.walks_used for e in envs)
        is_ss = spec.kind == "single_source"
        return ResultEnvelope(
            kind=spec.kind,
            nodes=spec.nodes,
            scores=np.stack([e.scores for e in envs]) if is_ss else None,
            topk_nodes=(
                None if is_ss else np.stack([e.topk_nodes for e in envs])
            ),
            topk_scores=(
                None if is_ss else np.stack([e.topk_scores for e in envs])
            ),
            walks_used=walks,
            latency_s=envs[0].latency_s,
            version=self.version,
            error_bound=self.error_bound(walks),
            variant=envs[0].variant,
            epsilon=spec.epsilon,
            certified_bound=worst.certified_bound,
            certificate=worst.certificate,
            rounds=max(e.rounds for e in envs),
            probe_levels=envs[0].probe_levels,
            push_path=envs[0].push_path,
            lanes=envs[0].lanes,
            walks_sampled=envs[0].walks_sampled,
        )

    def _serve_adaptive(
        self,
        batch: list[tuple],
        budget_walks: int | None = None,
        *,
        deadline_s: float | None = None,
    ) -> list[ResultEnvelope]:
        """Escalate one (possibly repeat-padded) batch until epsilon is met.

        Items are ``(spec, key)`` or ``(spec, key, ticket)`` tuples sharing
        one batch group.  Each round dispatches ONE fused single-source
        step (the same compiled lane-batched program flat serving uses —
        the loop lives outside it) under per-round ``fold_in(stream, r)``
        keys and folds the ``[Q, n]`` rows into the controller's carried
        accumulator; a query freezes at the round its certificate fires,
        so its answer is independent of how long batch mates escalate.
        The cap is ``spec.budget_walks`` (or the flat Thm-1 budget), which
        bounds total spend at the flat budget structurally.

        Hub queries (in-degree above ``hub_percentile``, ``spec.key`` not
        pinned) ride node-keyed streams and their rows go through the
        probe cache: a round whose rows are ALL resident skips its
        dispatch entirely (``stats.hub_hits``) — bitwise identical to
        serving, because cached rows were produced by the same streams.

        ``deadline_s`` is checked before every round after the first; on a
        miss the still-live queries freeze with ``certificate='deadline'``
        and their best-so-far scores — degradation, never an exception.
        """
        spec0 = batch[0][0]
        q = len(batch)
        conf = (
            spec0.confidence
            if spec0.confidence is not None
            else self.confidence
        )
        cap = spec0.budget_walks or budget_walks or self.params.n_r
        ctrl = AccuracyController(
            self.params,
            n=self.backend.n,
            q=q,
            epsilon=spec0.epsilon,
            confidence=conf,
            plan=escalation_schedule(min(self.initial_budget, cap), cap),
        )
        us = [item[0].node for item in batch]
        hubs = self.backend.hub_nodes(self.hub_percentile)
        streams, cacheable = [], []
        for item in batch:
            sp = item[0]
            if sp.key is None and sp.node in hubs:
                streams.append(jax.random.fold_in(self._hub_root, sp.node))
                cacheable.append(True)
            else:
                streams.append(item[1])
                cacheable.append(False)
        ver = self.version
        levels = walks = None  # summed over the rounds that dispatched
        path = lanes = None
        t0 = time.perf_counter()
        while True:
            n_round = ctrl.next_round()
            if n_round is None:
                ctrl.finish("budget")
                break
            r = ctrl.rounds_done
            if (
                deadline_s is not None
                and r > 0
                and time.perf_counter() - t0 >= deadline_s
            ):
                ctrl.finish("deadline")
                break
            # the row is bitwise-determined by (node stream, version,
            # round, round size) plus the lane geometry (q, walk_chunk)
            ckeys = [
                (us[i], ver, r, n_round, q, self.walk_chunk)
                if cacheable[i]
                else None
                for i in range(q)
            ]
            rows = [
                None if ck is None else self._probe_cache.get(ck)
                for ck in ckeys
            ]
            if rows and all(row is not None for row in rows):
                est = np.stack(rows)
                self.stats.hub_hits += 1  # a whole dispatch skipped
            else:
                keys = jnp.stack(
                    [jax.random.fold_in(s, r) for s in streams]
                )
                with span(DISPATCH):
                    est, _, _, lv = self.backend.serve_batch(
                        "single_source", us, keys, k=0, n_r=n_round
                    )
                est = np.asarray(est)
                if lv is not None:
                    levels = (levels or 0) + lv
                    self.stats.probe_levels += lv
                    path, lanes, w = self._note_dispatch(lv, q)
                    walks = None if w is None else (walks or 0) + w
                self.stats.steps += 1
                if r > 0:
                    self.stats.escalations += 1
                for i, ck in enumerate(ckeys):
                    if ck is not None:
                        self._probe_cache.put(ck, est[i])
            ctrl.absorb(n_round, est)
            if ctrl.all_frozen:
                break
        dt = time.perf_counter() - t0
        label = self.backend.dispatch_label("telescoped")
        out = []
        for i, item in enumerate(batch):
            sp = item[0]
            scores, cert = ctrl.result(i)
            env = ResultEnvelope(
                kind=sp.kind,
                node=sp.node,
                walks_used=cert.walks,
                latency_s=dt,
                version=ver,
                error_bound=self.error_bound(cert.walks),
                variant=label,
                epsilon=sp.epsilon,
                certified_bound=cert.bound,
                certificate=cert.name,
                rounds=cert.rounds,
                probe_levels=levels,
                push_path=path,
                lanes=None if lanes is None else lanes[i],
                walks_sampled=walks,
            )
            if sp.kind == "single_source":
                env.scores = scores
            else:
                # host top-k over the combined vector, matching the fused
                # epilogue's conventions: query node masked out, ties break
                # toward the lower index (stable argsort == lax.top_k)
                k = sp.k or self.top_k
                masked = scores.copy()
                masked[sp.node] = -np.inf
                order = np.argsort(-masked, kind="stable")[:k]
                env.topk_nodes = order.astype(np.int32)
                env.topk_scores = masked[order]
            out.append(env)
        return out

    # -- queued serving (submit -> fused drain) ------------------------------

    def submit(self, spec: QuerySpec | int) -> QueryTicket:
        """Enqueue a single-node spec (PRNG stream fixed NOW: batch-invariant).

        Returns a :class:`QueryTicket` — poll it, ``result()`` it, or
        ignore it and collect everything with :meth:`drain` as before.
        """
        spec = as_spec(spec, default_k=self.top_k)
        if spec.nodes is not None:
            raise ValueError("submit takes single-node specs; use query() "
                             "for an explicit batch")
        if spec.variant not in ("auto", "telescoped"):
            raise ValueError(
                "queued serving uses the fused telescoped path; "
                f"variant={spec.variant!r} is only available via query()"
            )
        with self._lock:
            if spec.key is not None:
                key, seq = spec.key, -1  # caller-pinned stream
            else:
                seq = self._seq
                key = self._query_key()
            ticket = QueryTicket(spec=spec, seq=seq, _session=self)
            self.query_queue.append((spec, key, ticket))
            return ticket

    def _batch_group(self, spec: QuerySpec):
        """Specs that can share one fused dispatch (same shapes/budget).

        Adaptive specs additionally group on (epsilon, confidence): every
        query in an escalation batch shares one controller, and flat specs
        never mix with adaptive ones.
        """
        return (
            spec.kind, spec.k, spec.budget_walks,
            spec.epsilon, spec.confidence,
        )

    def _pop_query_batch(self) -> tuple[list[tuple[QuerySpec, Array]], int]:
        """Pop up to ``batch_q`` group-compatible specs; repeat-pad the rest.

        Returns ``(batch, live)``: the batch keeps the static ``batch_q``
        shape, so one compiled step serves it, and ``live`` counts its
        popped specs.  A flat dispatch deals every lane column to those
        ``live`` queries and none to the padding slots (``core.multisource
        .lane_owner``), so a short batch runs in the levels its own walks
        need; epochs and adaptive rounds still run every slot.
        """
        gid = self._batch_group(self.query_queue[0][0])
        batch: list[tuple[QuerySpec, Array]] = []
        while (
            self.query_queue
            and len(batch) < self.batch_q
            and self._batch_group(self.query_queue[0][0]) == gid
        ):
            batch.append(self.query_queue.popleft())
        live = len(batch)
        while len(batch) < self.batch_q:
            batch.append(batch[-1])  # pad with repeats: static shape
        return batch, live

    def _serve_fused(
        self,
        batch: list[tuple],
        budget_walks: int | None,
        live: int | None = None,
    ) -> list[ResultEnvelope]:
        """One fused dispatch for a (possibly repeat-padded) query batch.

        Items are ``(spec, key)`` or ``(spec, key, ticket)`` tuples; the
        first ``live`` (None: all) are real and own every lane column, the
        rest repeat-pad the batch and get envelopes without an estimate.
        The returned envelope list is positional (tickets — when present —
        are filled by the caller for the live slice only, so repeat
        padding never double-assigns).  Adaptive groups (``epsilon`` set)
        route to the escalation loop instead of one flat dispatch, with
        every slot live.
        """
        spec0 = batch[0][0]
        if spec0.epsilon is not None:
            return self._serve_adaptive(batch, budget_walks)
        n_r = spec0.budget_walks or budget_walks or self.params.n_r
        us = [item[0].node for item in batch]
        keys = jnp.stack([item[1] for item in batch])
        with span(DISPATCH) as sp:
            est, idx, vals, levels = self.backend.serve_batch(
                spec0.kind, us, keys, k=spec0.k or 0, n_r=n_r, live=live
            )
        self.stats.steps += 1
        self.stats.probe_levels += levels or 0
        path, lanes, walks = self._note_dispatch(
            levels, len(batch) if live is None else live
        )
        ver = self.version
        bound = self.error_bound(n_r)
        return [
            ResultEnvelope(
                kind=spec0.kind,
                node=item[0].node,
                scores=None if est is None else est[i],
                topk_nodes=None if est is not None else idx[i],
                topk_scores=None if est is not None else vals[i],
                walks_used=n_r,
                latency_s=sp.seconds,
                version=ver,
                error_bound=bound,
                variant=self.backend.dispatch_label("telescoped"),
                probe_levels=levels,
                push_path=path,
                lanes=None if lanes is None else lanes[i],
                walks_sampled=walks,
            )
            for i, item in enumerate(batch)
        ]

    def _serve_next_batch(
        self, budget_walks: int | None
    ) -> list[ResultEnvelope]:
        """Pop + serve ONE fused batch; fills tickets for the live slice.

        Returns ``[]`` when the queue is already empty — a concurrent
        drain on another thread may have consumed it between our caller's
        check and the lock acquisition here.
        """
        with self._lock:
            if not self.query_queue:
                return []
            batch, live = self._pop_query_batch()
            served = self._serve_fused(batch, budget_walks, live)[:live]
            for item, env in zip(batch[:live], served):
                if len(item) > 2 and item[2] is not None:
                    item[2].envelope = env
            self.stats.queries += live
            return served

    def drain(self, *, budget_walks: int | None = None) -> list[ResultEnvelope]:
        """Serve every queued spec in fused batches of ``batch_q``.

        Consecutive group-compatible specs (same kind/k/budget) share a
        dispatch; short or cut batches are padded by repeating the last
        entry to keep one compiled shape (padded slots own no lane column
        and their rows are discarded).  ``budget_walks`` caps specs that
        don't pin their own.
        Tickets already forced via ``result()`` have left the queue — the
        returned list covers what was still queued, in order.
        """
        with self._lock:
            out: list[ResultEnvelope] = []
            while self.query_queue:
                out.extend(self._serve_next_batch(budget_walks))
            return out

    def _drain_until(
        self, ticket: QueryTicket, *, budget_walks: int | None = None
    ) -> None:
        """Serve queued batches (submission order) until ``ticket`` is done."""
        with self._lock:
            while ticket.envelope is None and self.query_queue:
                self._serve_next_batch(budget_walks)
            if ticket.envelope is None:
                raise RuntimeError(
                    "ticket is not queued in this session (was the queue "
                    "consumed by an epoch of a different session?)"
                )

    # -- immediate updates ---------------------------------------------------

    def _validate_ops(self, src: np.ndarray, dst: np.ndarray) -> None:
        # validate HERE: out-of-range ids would be sentinel-masked to no-ops
        # downstream and then mistaken for capacity-overflow skips, feeding
        # an unbounded retry/regrow loop
        n = self.backend.n
        bad = (src < 0) | (src >= n) | (dst < 0) | (dst >= n)
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"edge op ({src[i]}, {dst[i]}) out of range for n={n}"
            )

    @staticmethod
    def _as_ops(edges) -> tuple[np.ndarray, np.ndarray]:
        src, dst = edges
        return (np.asarray(src, np.int32).reshape(-1),
                np.asarray(dst, np.int32).reshape(-1))

    def update(self, inserts=None, deletes=None) -> UpdateReport:
        """Apply edge updates NOW through the coordinated both-mirrors path.

        ``inserts``/``deletes`` are ``(src, dst)`` array pairs; inserts
        apply before deletes within one call.  Deleting duplicate (s, d)
        pairs in one call removes one copy per op (multigraph semantics):
        the batch path deletes at most one copy per batch, so duplicates
        are split into per-occurrence sub-batches (vectorized — see
        ``_occurrence_numbers``).  Batches are padded to the next power of
        two so variable-size bursts reuse a log-bounded set of compiled
        shapes.  With ``auto_regrow``, overflow-skipped inserts trigger a
        regrow and are retried until applied; otherwise they are surfaced
        in ``UpdateReport.skipped``.
        """
        with self._lock:
            rep = UpdateReport()
            if inserts is not None:
                s, d = self._as_ops(inserts)
                self._validate_ops(s, d)
                self._apply_now(s, d, True, rep)
            if deletes is not None:
                s, d = self._as_ops(deletes)
                self._validate_ops(s, d)
                if s.shape[0]:
                    occ = _occurrence_numbers(s, d, self.backend.n)
                    for k in range(int(occ.max()) + 1):
                        m = occ == k
                        self._apply_now(s[m], d[m], False, rep)
            rep.version = self.version
            rep.overflow = self.overflow
            return rep

    def _apply_now(
        self, src: np.ndarray, dst: np.ndarray, insert: bool, rep: UpdateReport
    ) -> None:
        if src.shape[0] == 0:
            return
        rep.submitted += int(src.shape[0])
        while True:
            # the backend pads/buckets internally (pow-2 batches on the
            # local path; shard-wise re-partition on the sharded path)
            applied = self.backend.apply_ops(src, dst, insert)
            n_app = int(applied.sum())
            rep.applied += n_app
            self.stats.updates += n_app
            if not insert:
                return  # unapplied deletes were genuinely absent: no retry
            skipped = ~applied
            if not skipped.any():
                return
            if not self.auto_regrow:
                rep.skipped += [
                    (int(s), int(d), True)
                    for s, d in zip(src[skipped], dst[skipped])
                ]
                return
            self.backend.regrow()  # 2x buffers per round: terminates
            self.stats.regrows += 1
            rep.regrows += 1
            src, dst = src[skipped], dst[skipped]

    # -- fused update->query epochs ------------------------------------------

    def queue_update(self, src, dst, *, insert: bool = True) -> None:
        """Enqueue edge ops for the next :meth:`epoch` step(s)."""
        s, d = self._as_ops((src, dst))
        self._validate_ops(s, d)
        with self._lock:
            for a, b in zip(s, d):
                self.update_queue.append((int(a), int(b), insert))

    def _pop_updates(self) -> tuple[list[tuple[int, int, bool]], UpdateBatch]:
        # apply_update_batch runs its delete phase before its insert phase
        # and deletes at most one copy of a (s, d) pair per batch, so a batch
        # must not contain (a) a delete of an edge inserted earlier in the
        # SAME batch, nor (b) a second delete of the same pair (multigraph
        # copies) — cut the epoch's batch there (the delete waits for the
        # next epoch) to preserve exact stream order
        ops: list[tuple[int, int, bool]] = []
        inserted: set[tuple[int, int]] = set()
        deleted: set[tuple[int, int]] = set()
        while self.update_queue and len(ops) < self.update_batch:
            s, d, ins = self.update_queue[0]
            if not ins and ((s, d) in inserted or (s, d) in deleted):
                break
            (inserted if ins else deleted).add((s, d))
            ops.append(self.update_queue.popleft())
        batch = make_update_batch(
            [s for s, _, _ in ops],
            [d for _, d, _ in ops],
            [i for _, _, i in ops] if ops else True,
            batch_size=self.update_batch,
            n=self.backend.n,
        )
        return ops, batch

    def _pop_epoch_queries(self) -> tuple[int, list, QuerySpec]:
        qs, live = self._pop_query_batch()  # same grouping/padding as drain
        return live, qs, qs[0][0]

    def epoch(
        self,
        *,
        inserts=None,
        deletes=None,
        queries=None,
        budget_walks: int | None = None,
    ) -> EpochResult:
        """Run ONE fused epoch: up to ``update_batch`` queued ops + up to
        ``batch_q`` queued queries in a single compiled dispatch.

        ``inserts``/``deletes`` (``(src, dst)`` pairs) and ``queries``
        (node ids or single-node specs) are enqueued first — anything past
        one epoch's width stays queued (see :attr:`pending`; loop epochs to
        drain).  Scores are exact w.r.t. the post-update snapshot (zero
        host transfers between update and query); a top-k query batch runs
        the fused top-k epilogue, a single_source batch returns full score
        vectors.  Update-only epochs (empty query queue) dispatch just the
        batch application — no point paying the fused probe for discarded
        dummy queries.
        """
        if not getattr(self.backend, "supports_epoch", False) or not hasattr(
            self.backend, "epoch_batch"
        ):
            # capability detection: the epoch is a Backend-protocol stage
            # now — a backend that doesn't implement it gets update() +
            # drain() instead of a fused step
            raise NotImplementedError(
                f"the {self.backend.name!r} backend does not implement "
                "epoch_batch; apply update() and drain() separately"
            )
        if not self._owns_graph:
            # the epoch step DONATES graph buffers; with own_graph=False
            # the caller kept the handle authoritative and shares its
            # arrays with the session (CPU ignores donation, so this would
            # pass tests and corrupt in production)
            raise ValueError(
                "epoch() requires an owned graph: construct the session "
                "from a GraphHandle with own_graph=True (the default)"
            )
        with self._lock:
            return self._epoch_locked(
                inserts=inserts, deletes=deletes, queries=queries,
                budget_walks=budget_walks,
            )

    def _epoch_locked(
        self, *, inserts, deletes, queries, budget_walks
    ) -> EpochResult:
        if inserts is not None:
            self.queue_update(*self._as_ops(inserts), insert=True)
        if deletes is not None:
            self.queue_update(*self._as_ops(deletes), insert=False)
        if queries is not None:
            for q in queries:
                self.submit(q)
        if self.query_queue and self.query_queue[0][0].epsilon is not None:
            # the escalation loop lives OUTSIDE the compiled step (it must
            # inspect per-round scores on host), so it cannot ride the
            # fused update->query epoch; the specs stay queued
            raise ValueError(
                "adaptive (epsilon) specs cannot be served inside a fused "
                "epoch — apply the update, then serve them via drain() or "
                "query()"
            )
        ops, batch = self._pop_updates()
        p = self.params

        t0 = time.time()
        if self.query_queue:
            live_q, qs, spec0 = self._pop_epoch_queries()
            n_r = spec0.budget_walks or budget_walks or p.n_r
            tk = spec0.k if spec0.kind == "topk" else 0
            us = [item[0].node for item in qs]
            keys = jnp.stack([item[1] for item in qs])
            applied, est, idx, vals = self.backend.epoch_batch(
                batch, us, keys,
                n_r=n_r, top_k=tk,
                lanes=self.walk_chunk,
            )
        else:
            live_q, qs, spec0 = 0, [], None
            n_r = budget_walks or p.n_r
            applied, est, idx, vals = self.backend.epoch_batch(
                batch, None, None,
                n_r=n_r, top_k=0,
                lanes=self.walk_chunk,
            )
        applied = np.asarray(applied)[: len(ops)]
        dt = time.time() - t0

        version = self.version
        overflow = self.overflow
        regrown = False
        requeued = 0
        # skipped inserts (applied == False); unapplied deletes were
        # genuinely absent — those are not retried or surfaced
        skipped = [op for op, ok in zip(ops, applied) if not ok and op[2]]
        if skipped and self.auto_regrow:
            # retry on the regrown buffers next epoch
            for op in reversed(skipped):
                self.update_queue.appendleft(op)
            requeued = len(skipped)
            self.backend.regrow()
            self.stats.regrows += 1
            regrown = True

        bound = self.error_bound(n_r)
        variant = self.backend.epoch_dispatch_label()
        results = [
            ResultEnvelope(
                kind=spec0.kind,
                node=item[0].node,
                scores=None if est is None else est[i],
                topk_nodes=None if est is not None else idx[i],
                topk_scores=None if est is not None else vals[i],
                walks_used=n_r,
                latency_s=dt,
                version=version,
                error_bound=bound,
                variant=variant,
            )
            for i, item in enumerate(qs[:live_q])
        ]
        for item, env in zip(qs[:live_q], results):
            if len(item) > 2 and item[2] is not None:
                item[2].envelope = env
        self.stats.epochs += 1
        self.stats.steps += 1
        self.stats.queries += live_q
        self.stats.updates += int(applied.sum())
        return EpochResult(
            version=version,
            overflow=overflow,
            regrown=regrown,
            updates_submitted=len(ops),
            updates_applied=int(applied.sum()),
            updates_requeued=requeued,
            skipped_ops=skipped,
            results=results,
            latency_s=dt,
        )

    def drain_epochs(
        self, *, budget_walks: int | None = None
    ) -> list[EpochResult]:
        """Run epochs until both queues are empty."""
        with self._lock:
            out: list[EpochResult] = []
            while self.update_queue or self.query_queue:
                out.append(self.epoch(budget_walks=budget_walks))
            return out
