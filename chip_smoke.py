"""Run the ProbeSim serving path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases a-e below
    python chip_smoke.py --chips 4   # four chips: the sharded backend only

One chip:

  a. the device is a TPU (JAX falls back to the CPU in silence otherwise);
  b. correctness: the ``wiki-vote`` stand-in at full scale, the Power
     Method on the chip at HIGHEST precision, and 16 single-source queries
     drained as one fused dispatch — every query's error must sit inside
     its envelope's ``error_bound``;
  c. serving at real size: the ``hepph`` stand-in at its published n —
     one query of each kind, a 16-query drain, an update burst, and a fused
     update->query epoch compared with a session on the rebuilt graph;
  d. the HTTP service on the same graph: 32 queries from 8 client threads
     and one update;
  e. one CSR lane-probe level (the Pallas kernel the TPU serve path
     runs), compiled natively, against the XLA level.

Four chips: the sharded backend on a (1, 4) mesh over the hepph graph with
the spmd and ring probes and one mesh epoch, each against the local backend
under the same keys.

Every phase prints one JSON line (realized graph size, wall time including
compilation, device memory, differences against the reference).  Any failed
check raises and the script exits non-zero; the last line of a passing run
is ``{"ok": true, "device": {...}}``.  The script starts no subprocess, and
must run from a checkout of the repository (it imports ``src/repro``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

BATCH_Q = 16
EPS_A = 0.1
SEED = 0
# serving-phase walk budget per query (the service's default_budget_walks
# and the anytime cap of the phase-c queries); None = the flat Thm-1 budget
SERVE_BUDGET = None
SERVICE_BUDGET = 1024
# (nodes, edges, lanes): mean in-degree 12 as at hepph, the serve width,
# and a hub row of m/4 edges that spans two id chunks
KERNEL_SHAPE = (4096, 49152, 256)
EPOCH_TOL = 1e-6  # fused epoch vs rebuilt-graph session, same keys
KERNEL_TOL = 1e-5  # native CSR level vs the XLA level (sums reorder)
MESH_TOL = 1e-4  # sharded vs local backend (the fake-mesh test tolerance)


_T0 = time.time()


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _progress(step: str) -> None:
    """A timestamped step on stderr: where a slow or cut run got to."""
    print(f"[{time.time() - _T0:8.1f}s] {step}", file=sys.stderr, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _memory() -> dict:
    """Device-0 memory counters (None where the backend keeps none)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _graph_stats(src, dst, n) -> dict:
    indeg = np.bincount(dst, minlength=n)
    return dict(n=int(n), m=int(len(src)), max_in_degree=int(indeg.max()))


def _sources(dst, n, count, seed) -> list[int]:
    """Query nodes with in-neighbors, so their scores are not all zero."""
    rng = np.random.default_rng(seed)
    live = np.flatnonzero(np.bincount(dst, minlength=n) > 0)
    return [int(u) for u in rng.choice(live, size=count, replace=False)]


def _handle(src, dst, n, *, spare_edges: int = 4096):
    """Handle with COO room for the update phases; the ELL width is the
    max in-degree, so updates must avoid the widest rows."""
    from repro.api import GraphHandle

    k_max = int(np.bincount(dst, minlength=n).max())
    return GraphHandle.from_edges(
        src, dst, n, capacity=len(src) + spare_edges, k_max=k_max
    )


def _update_ops(src, dst, n, *, inserts: int, deletes: int, seed: int):
    """Inserts into rows with ELL room to spare, deletes of live edges."""
    rng = np.random.default_rng(seed)
    indeg = np.bincount(dst, minlength=n)
    roomy = np.flatnonzero(indeg < indeg.max() - inserts)
    ins = (rng.integers(0, n, inserts).astype(np.int32),
           rng.choice(roomy, inserts).astype(np.int32))
    pick = rng.choice(len(src), deletes, replace=False)
    dels = (np.asarray(src)[pick].astype(np.int32),
            np.asarray(dst)[pick].astype(np.int32))
    return ins, dels


def _pinned_specs(sources, kind: str, seed: int):
    import jax

    from repro.api import QuerySpec

    root = jax.random.key(seed)
    return [
        QuerySpec(kind=kind, node=u, key=jax.random.fold_in(root, i))
        for i, u in enumerate(sources)
    ]


def _drain_scores(sess, specs, budget_walks=None) -> np.ndarray:
    """Serve ``specs`` through submit/drain as ONE fused dispatch."""
    steps = sess.stats.steps
    tickets = [sess.submit(s) for s in specs]
    sess.drain(budget_walks=budget_walks)
    _check(sess.stats.steps == steps + 1,
           f"drain of {len(specs)} took {sess.stats.steps - steps} dispatches")
    return np.stack([np.asarray(t.envelope.scores) for t in tickets])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    """a. The default device must be a TPU."""
    import jax

    d = jax.devices()[0]
    _check(d.platform == "tpu", f"JAX runs on {d.platform!r}, not a TPU")
    info = dict(platform=d.platform, kind=d.device_kind,
                count=len(jax.devices()))
    _emit("a_device", **info)
    return info


def phase_reference(src, dst, n, *, queries=BATCH_Q, budget_walks=None,
                    seed=SEED) -> dict:
    """b. 16 fused single-source queries vs the on-chip Power Method."""
    from repro.api import QuerySpec, SimRankSession
    from repro.core.power import simrank_power

    t0 = time.time()
    _progress("b: power method")
    h = _handle(src, dst, n)
    truth = np.asarray(simrank_power(h.g, c=0.6))
    t_ref = time.time() - t0
    sess = SimRankSession(h, eps_a=EPS_A, batch_q=BATCH_Q, seed=seed,
                          own_graph=False)
    sources = _sources(dst, n, queries, seed)
    tickets = [sess.submit(QuerySpec(kind="single_source", node=u))
               for u in sources]
    _progress("b: fused drain")
    sess.drain(budget_walks=budget_walks)
    _check(sess.stats.steps == 1, f"drain took {sess.stats.steps} dispatches")
    errs, bounds = [], []
    for u, t in zip(sources, tickets):
        est = np.asarray(t.envelope.scores)
        _check(est.shape == (n,) and np.isfinite(est).all(),
               f"query {u}: scores not finite of shape ({n},)")
        err = np.abs(est - truth[u])
        err[u] = 0.0
        errs.append(float(err.max()))
        bounds.append(float(t.envelope.error_bound))
    top = sess.query(QuerySpec(kind="topk", node=sources[0], k=min(10, n - 1)),
                     budget_walks=budget_walks)
    _check(sources[0] not in np.asarray(top.topk_nodes).tolist(),
           "top-k answer contains its own source")
    out = dict(**_graph_stats(src, dst, n), queries=queries,
               walks=int(tickets[0].envelope.walks_used),
               max_abs_err=max(errs), error_bound=min(bounds),
               per_query_err=errs, reference_s=t_ref,
               seconds=time.time() - t0, **_memory())
    _emit("b_reference", **out)
    _check(all(e <= b for e, b in zip(errs, bounds)),
           "a query's error exceeds its envelope's error_bound")
    return out


def phase_serving(src, dst, n, *, budget_walks=SERVE_BUDGET,
                  seed=SEED) -> dict:
    """c. Queries, a fused drain, an update burst and a fused epoch at
    real size; the epoch is compared with a rebuilt-graph session."""
    from repro.api import QuerySpec, SimRankSession

    t0 = time.time()
    h = _handle(src, dst, n)
    # the session owns its copy (epochs donate the mirror buffers); drop
    # the caller's so one graph copy stays on the device
    sess = SimRankSession(h, eps_a=EPS_A, batch_q=BATCH_Q, seed=seed)
    del h
    gc.collect()
    sources = _sources(dst, n, BATCH_Q, seed + 1)
    u = sources[0]
    out = dict(**_graph_stats(src, dst, n))

    t = time.time()
    _progress("c: one query of each kind")
    ss = sess.query(QuerySpec(kind="single_source", node=u),
                    budget_walks=budget_walks)
    tk = sess.query(QuerySpec(kind="topk", node=u), budget_walks=budget_walks)
    est = np.asarray(ss.scores)
    _check(est.shape == (n,) and np.isfinite(est).all() and est[u] == 1.0,
           "single-source answer malformed")
    nodes = np.asarray(tk.topk_nodes).tolist()
    vals = np.asarray(tk.topk_scores)
    _check(u not in nodes and len(nodes) == sess.top_k
           and np.isfinite(vals).all() and (np.diff(vals) <= 0).all(),
           "top-k answer malformed")
    out.update(query_s=time.time() - t, query_variants=[ss.variant, tk.variant])

    t = time.time()
    _progress("c: fused drain")
    scores = _drain_scores(
        sess, [QuerySpec(kind="single_source", node=v) for v in sources],
        budget_walks,
    )
    _check(np.isfinite(scores).all(), "drained scores not finite")
    out.update(drain_s=time.time() - t, drain_walks=int(
        budget_walks or sess.params.n_r))

    t = time.time()
    _progress("c: update burst")
    v0 = sess.version
    ins, dels = _update_ops(src, dst, n, inserts=64, deletes=16, seed=seed)
    rep = sess.update(inserts=ins, deletes=dels)
    _check(rep.applied == 80 and rep.regrows == 0,
           f"update applied {rep.applied}/80 ops, {rep.regrows} regrows")
    after = sess.query(QuerySpec(kind="topk", node=u),
                       budget_walks=budget_walks)
    _check(sess.version > v0 and after.version == sess.version,
           "version did not advance with the update")
    out.update(update_s=time.time() - t, versions=[v0, sess.version])

    # fused epoch: inserts + deletes + 16 pinned-key queries, one dispatch
    t = time.time()
    _progress("c: fused epoch")
    live_s, live_d = sess.handle.to_host_edges()
    ins, dels = _update_ops(live_s, live_d, n, inserts=24, deletes=8,
                            seed=seed + 2)
    specs = _pinned_specs(sources, "single_source", seed + 3)
    v1 = sess.version
    ep = sess.epoch(inserts=ins, deletes=dels, queries=specs,
                    budget_walks=budget_walks)
    _check(ep.updates_applied == 32 and not ep.regrown
           and len(ep.results) == BATCH_Q and ep.version == v1 + 1,
           f"epoch applied {ep.updates_applied}/32 ops at v{ep.version}")
    epoch_scores = np.stack([np.asarray(r.scores) for r in ep.results])
    out.update(epoch_s=time.time() - t)

    # rebuild from the live edges and serve the same keys; release the
    # epoch session first (one graph copy on the device at a time)
    e_src, e_dst = sess.handle.to_host_edges()
    cap, k_max = sess.handle.capacity, sess.handle.k_max
    del sess, ep
    gc.collect()
    from repro.api import GraphHandle

    _progress("c: rebuilt-graph session")
    h2 = GraphHandle.from_edges(e_src, e_dst, n, capacity=cap, k_max=k_max)
    sess2 = SimRankSession(h2, eps_a=EPS_A, batch_q=BATCH_Q, own_graph=False)
    rebuilt = _drain_scores(sess2, specs, budget_walks)
    diff = float(np.abs(epoch_scores - rebuilt).max())
    out.update(epoch_vs_rebuild_max_abs=diff, seconds=time.time() - t0,
               **_memory())
    del sess2, h2
    gc.collect()
    _emit("c_serving", **out)
    _check(diff <= EPOCH_TOL, f"epoch vs rebuild differ by {diff}")
    return out


def phase_service(src, dst, n, *, clients=8, per_client=4,
                  budget_walks=SERVICE_BUDGET, seed=SEED) -> dict:
    """d. The HTTP service: concurrent /query traffic and one /update."""
    from repro.serving import ServiceConfig, SimRankService
    from repro.serving.server import ServiceClient, start_server, stop_server

    t0 = time.time()
    _progress("d: service")
    h = _handle(src, dst, n)
    svc = SimRankService(
        h, seed=seed,
        config=ServiceConfig(max_batch_q=BATCH_Q, batch_window_ms=50.0,
                             default_budget_walks=budget_walks),
        session_kwargs=dict(eps_a=EPS_A),
    )
    del h  # the service keeps its own copy
    gc.collect()
    server, thread = start_server(svc, port=0)
    host, port = server.server_address
    sources = _sources(dst, n, clients * per_client, seed + 4)
    statuses, versions = [], []
    lock = threading.Lock()
    try:
        with ServiceClient(host, port) as cl:  # compile before the burst
            status, warm = cl.query_raw(node=sources[0], kind="topk", k=10)
            _check(status == 200, f"warm-up query -> {status}")
        t = time.time()
        barrier = threading.Barrier(clients)

        def client(i):
            with ServiceClient(host, port) as cl:
                barrier.wait()  # land the first wave in one window
                for j in range(per_client):
                    u = sources[i * per_client + j]
                    st, payload = cl.query_raw(node=u, kind="topk", k=10)
                    ok = st == 200 and u not in payload.get("topk_nodes", [u])
                    with lock:
                        statuses.append(st if ok else -st)
                        versions.append(payload.get("version"))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        burst_s = time.time() - t
        ins, _ = _update_ops(src, dst, n, inserts=8, deletes=0, seed=seed + 5)
        with ServiceClient(host, port) as cl:
            rep = cl.update(inserts=list(zip(*ins)))
            later_status, later = cl.query_raw(node=sources[1], kind="topk",
                                               k=10)
            stats = cl.stats()["service"]
    finally:
        stop_server(server, thread)
        del svc, server
        gc.collect()
    hist = {int(k): v for k, v in stats["batch_hist"].items()}
    out = dict(**_graph_stats(src, dst, n), requests=len(statuses),
               statuses=sorted(set(statuses)), burst_s=burst_s,
               batch_hist=hist, update_applied=rep.get("applied"),
               version_before=warm["version"], version_after=later.get(
                   "version"), seconds=time.time() - t0, **_memory())
    _emit("d_service", **out)
    _check(len(statuses) == clients * per_client
           and set(statuses) == {200}, f"non-200 answers: {statuses}")
    _check(later_status == 200 and max(hist) > 1,
           f"no micro-batch above 1: {hist}")
    _check(later["version"] > warm["version"],
           "a query after /update did not see the new version")
    return out


def phase_kernel(n=KERNEL_SHAPE[0], m=KERNEL_SHAPE[1], w=KERNEL_SHAPE[2],
                 seed=SEED) -> dict:
    """e. One CSR lane-probe level, native on a TPU, vs the XLA level."""
    import jax
    import jax.numpy as jnp

    from repro.core.multisource import xla_level
    from repro.graph.structs import graph_from_edges
    from repro.kernels.lane_probe.ops import (
        csr_layout, csr_push_view, lane_probe_csr_level,
    )

    t0 = time.time()
    _progress("e: kernel")
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, n, m)
    dst[: m // 4] = 7  # a hub row
    g = graph_from_edges(rng.integers(0, n, m), dst, n)
    sqrt_c = 0.6 ** 0.5
    # a mid-probe level at serve-path magnitudes: frontier mass in [0, 1)
    scores = rng.random((n + 1, w)).astype(np.float32)
    total = rng.random((n + 1, w)).astype(np.float32)
    scores[n] = total[n] = 0.0  # the dump row
    lane = [
        rng.random(w) < 0.3,  # fin
        np.where(rng.random(w) < 0.5, rng.integers(0, n, w), n).astype(
            np.int32),  # u_p
        np.where(rng.random(w) < 0.5, rng.integers(0, n, w), n).astype(
            np.int32),  # u_prev
        (rng.random(w) * 1e-3).astype(np.float32),  # thr
    ]
    scores, total, *lane = map(jnp.asarray, (scores, total, *lane))
    rows, _ = csr_layout(n)
    grow = ((0, rows - n - 1), (0, 0))

    def fused(g, scores, total, *lane):
        view = csr_push_view(g, g.inv_in_deg * sqrt_c, rows=rows)
        return lane_probe_csr_level(
            view, jnp.pad(scores, grow), jnp.pad(total, grow), *lane,
            prune=True,
        )

    fused = jax.jit(fused)
    ref = jax.jit(lambda g, *a: xla_level(
        g, *a, cols=jnp.arange(w), sqrt_c=sqrt_c, prune=True
    ))
    args = (g, scores, total, *lane)
    out, tot = fused(*args)
    r_out, r_tot = ref(*args)
    d_out = float(jnp.abs(out[: n + 1] - r_out).max())
    d_tot = float(jnp.abs(tot[: n + 1] - r_tot).max())
    native = "tpu_custom_call" in fused.lower(*args).compile().as_text()
    res = dict(shape=dict(nodes=n, edges=m, lanes=w), native=native,
               max_abs_diff_scores=d_out, max_abs_diff_total=d_tot,
               max_abs_scores=float(jnp.abs(r_out).max()),
               seconds=time.time() - t0, **_memory())
    _emit("e_kernel", **res)
    _check(native or jax.default_backend() != "tpu",
           "the kernel did not compile to a TPU custom call")
    _check(max(d_out, d_tot) <= KERNEL_TOL,
           f"kernel differs from the XLA level by {max(d_out, d_tot)}")
    return res


def phase_sharded(src, dst, n, *, shards=4, budget_walks=SERVE_BUDGET,
                  seed=SEED) -> dict:
    """Four chips: spmd and ring sharded serving and one mesh epoch, each
    against the local backend under the same keys."""
    from repro.api import SimRankSession
    from repro.utils.jaxcompat import make_mesh

    t0 = time.time()
    mesh = make_mesh((1, shards), ("data", "model"))
    sources = _sources(dst, n, BATCH_Q, seed + 6)
    specs = _pinned_specs(sources, "single_source", seed + 7)
    h = _handle(src, dst, n)
    local = SimRankSession(h, eps_a=EPS_A, batch_q=BATCH_Q)  # owns a copy
    sharded = {
        probe: SimRankSession(h, eps_a=EPS_A, batch_q=BATCH_Q,
                              backend="sharded", mesh=mesh,
                              backend_options=dict(probe=probe))
        for probe in ("spmd", "ring")
    }
    del h  # the sessions hold their own graph state
    gc.collect()
    ref = _drain_scores(local, specs, budget_walks)
    out = dict(**_graph_stats(src, dst, n), shards=shards)
    for probe, sess in sharded.items():
        t = time.time()
        _progress(f"sharded: {probe} drain")
        got = _drain_scores(sess, specs, budget_walks)
        st = sess.backend._epoch_graph_state()
        spans = {f: len(getattr(st, f).sharding.device_set)
                 for f in ("src_sh", "dst_sh", "counts", "in_nbrs", "in_deg")}
        out[probe] = dict(max_abs_diff=float(np.abs(got - ref).max()),
                          devices=spans, seconds=time.time() - t)
        _check(all(v == shards for v in spans.values()),
               f"{probe}: carried mirror does not span {shards} devices: "
               f"{spans}")
    mesh_sess = sharded.pop("spmd")
    del sharded
    gc.collect()
    # one mesh epoch vs one local epoch: same ops, same keys
    t = time.time()
    _progress("sharded: epochs")
    ins, dels = _update_ops(src, dst, n, inserts=24, deletes=8, seed=seed + 8)
    espec = _pinned_specs(sources, "single_source", seed + 9)
    ep_l = local.epoch(inserts=ins, deletes=dels, queries=espec,
                       budget_walks=budget_walks)
    ep_m = mesh_sess.epoch(inserts=ins, deletes=dels, queries=espec,
                           budget_walks=budget_walks)
    _check(ep_l.updates_applied == ep_m.updates_applied == 32,
           "epochs applied different op counts")
    diff = max(float(np.abs(np.asarray(a.scores) - np.asarray(b.scores))
                     .max()) for a, b in zip(ep_l.results, ep_m.results))
    st = mesh_sess.backend._epoch_graph_state()
    spans = {f: len(getattr(st, f).sharding.device_set)
             for f in ("src_sh", "dst_sh", "counts", "in_nbrs", "in_deg")}
    out["epoch"] = dict(max_abs_diff=diff, devices=spans,
                        seconds=time.time() - t)
    out.update(seconds=time.time() - t0, **_memory())
    _emit("sharded", **out)
    _check(all(out[p]["max_abs_diff"] <= MESH_TOL for p in ("spmd", "ring")),
           "sharded serving differs from local beyond tolerance")
    _check(diff <= MESH_TOL, f"mesh epoch differs from local by {diff}")
    _check(all(v == shards for v in spans.values()),
           f"epoch mirror does not span {shards} devices: {spans}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a (1, 4) mesh")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit("chip_smoke.py must run from a checkout of the repository "
                 f"({SRC}/repro not found)")
    sys.path.insert(0, SRC)
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    info = phase_device()  # before any output: off a TPU, print nothing
    _emit("setup", compile_cache=cache)
    if info["count"] < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} devices, "
                 f"JAX sees {info['count']}")

    from repro.graph import paper_dataset

    hepph = paper_dataset("hepph", 1.0, seed=SEED)
    if args.chips == 4:
        phase_sharded(*hepph)
    else:
        phase_reference(*paper_dataset("wiki-vote", 1.0, seed=SEED))
        phase_serving(*hepph)
        phase_service(*hepph)
        phase_kernel()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
