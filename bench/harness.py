"""One benchmark run: a cell's graph, its service, its traffic, its checks.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<traffic>.json`` and, for each per-layer
metric, ``metrics/<name>.py`` (a module with ``read(ctx)`` that returns a
number or None).  Adding a cell or a metric adds files; this module does
not change.

A run goes through these steps (``run()``):

1. enable JAX's compile cache (``repro.utils.compile_cache``) and refuse
   to go on without an accelerator holding the chips the cell asks for;
2. generate the configuration's graph on the host from the seed;
3. build the ``GraphHandle``, start ``SimRankService`` behind the HTTP
   server on loopback, and warm the cell's own shapes: one top-k query
   (the Q-padded dispatch) and, where the traffic has updates, one burst;
4. start the load generator (``loadgen.py``, a standard-library child
   process) on the window's open-loop schedule;
5. measure for ``seconds`` (tracing from the first send to the last
   answer where ``trace``), wait up to a minute past the close for late
   answers, read the device's peak memory, stop the service, compare the
   answers with the plain reference, and return the result line.
"""
from __future__ import annotations

import gc
import http.client
import importlib.util
import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time
from collections import Counter
from types import SimpleNamespace

import numpy as np

import check
import graphgen
import schedule
import tracereduce

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
GRACE_S = 60.0  # how long past the close late answers are waited for
LEAD_S = 1.0  # from handing the plan to the generator to its first send
VERSIONS_CHECKED = 6  # graph versions whose answers the churn check reads,
ANSWERS_CHECKED = 24  # and more versions until it reads at least this many
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> SimpleNamespace:
    """The cell's entry, configuration, traffic and metric entries."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    bench_dir = os.path.join(root, os.path.dirname(
        os.path.dirname(cfg_entry["file"])))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return SimpleNamespace(
        name=workload, cell=cell, chips=int(cell["chips"]),
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(
            bench_dir, "traffic", cell["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def load_module(bench_dir: str, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module: a metric's reader
    (``metrics``) or a configuration's reference (``configs``)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def post(host: str, port: int, path: str, body) -> tuple[int, dict]:
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    try:
        if body is None:
            conn.request("GET", path)
        else:
            conn.request("POST", path, body=json.dumps(body).encode(),
                         headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def p90(values) -> float:
    """Nearest-rank 90th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.9 * len(v)) - 1)] if v else float("nan")


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def start_jax(chips: int, *, require_chip: bool, cache: bool):
    """Import the program's JAX, enable the compile cache, check devices."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if cache:
        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program, however quick to compile, comes from the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu" or len(devices) < chips):
        raise SystemExit(
            f"need {chips} accelerator chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)")
    return jax, devices


class Service:
    """The cell's graph behind ``SimRankService`` and its HTTP server."""

    def __init__(self, c: SimpleNamespace, seed: int):
        from repro.api import GraphHandle
        from repro.serving import ServiceConfig, SimRankService
        from repro.serving.server import start_server

        g, guar, svc_cfg = c.config["graph"], c.config["guarantee"], \
            c.config["service"]
        n, m = g["n"], g["m"]
        self.src, self.dst = graphgen.generate(
            n, m, g["in_degree_exponent"], seed, g.get("in_edge_nodes"))
        self.n = n
        deg = np.bincount(self.dst, minlength=n)
        self.k_max = int(deg.max()) + int(g["ell_headroom"])
        self.capacity = m + int(g["coo_spare"])
        self.graph = graphgen.stats(self.src, self.dst, n, k_max=self.k_max,
                                    capacity=self.capacity)
        self.sources = np.flatnonzero(deg > 0)
        h = GraphHandle.from_edges(self.src, self.dst, n,
                                   capacity=self.capacity, k_max=self.k_max)
        self.svc = SimRankService(
            h, seed=seed % 2**31,
            config=ServiceConfig(
                max_batch_q=svc_cfg["max_batch_q"],
                batch_window_ms=svc_cfg["batch_window_ms"],
                max_inflight=svc_cfg["max_inflight"],
                default_budget_walks=None),
            session_kwargs=dict(c=guar["c"], eps_a=guar["eps_a"],
                                delta=guar["delta"],
                                walk_chunk=svc_cfg["lanes"],
                                top_k=guar["top_k"]))
        del h  # the service keeps its own copy
        gc.collect()
        self.server, self.thread = start_server(self.svc, "127.0.0.1", 0)
        self.host, self.port = self.server.server_address[:2]

    def stats(self) -> dict:
        status, body = post(self.host, self.port, "/stats", None)
        if status != 200:
            raise RuntimeError(f"GET /stats -> {status}")
        return body

    def device_edges(self):
        return self.svc.session().handle.to_host_edges()

    def stop(self) -> None:
        from repro.serving.server import stop_server

        stop_server(self.server, self.thread)
        self.svc = self.server = None
        gc.collect()


def churn_for(c: SimpleNamespace, svc: Service, seed: int):
    u = c.traffic.get("updates")
    if not u:
        return None
    g = c.config["graph"]
    p = graphgen.in_degree_weights_by_node(
        svc.n, g["m"], g["in_degree_exponent"], svc.dst, g.get("in_edge_nodes"))
    return schedule.Churn(svc.src, svc.dst, svc.n, p, seed,
                          u["inserts"], u["deletes"])


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


def drive(svc: Service, reqs: list, seconds: float, *, workers: int,
          trace_dir: str | None = None) -> tuple[float, list]:
    """Run ``reqs`` open-loop from a child process; returns ``(t0, results)``.

    With ``trace_dir``, the run is traced into it from the first scheduled
    send until the last answer is in (or given up): every dispatch of the
    window's queries lies wholly inside that span."""
    import jax

    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        t0 = time.monotonic() + LEAD_S
        plan = {"host": svc.host, "port": svc.port, "t0": t0,
                "workers": workers, "give_up": seconds + GRACE_S,
                "requests": reqs}
        child.stdin.write(json.dumps(plan).encode())
        child.stdin.close()
        if trace_dir is None:
            out = child.stdout.read()
        else:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                time.sleep(max(0.0, t0 - time.monotonic()))
                with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
                    out = child.stdout.read()
            finally:
                jax.profiler.stop_trace()
        if child.wait() != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    return t0, json.loads(out)["results"]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, root: str = ROOT,
        require_chip: bool = True, cache: bool = True,
        budget_walks: int | None = None) -> dict:
    """One run of ``workload``; prints info lines and returns the result.

    ``budget_walks`` puts every query on the program's anytime walk budget
    (the control of ``control.py``); benchmark runs leave it unset."""
    t_start = time.monotonic() if t_start is None else t_start
    c = load_cell(workload, root)
    jax, devices = start_jax(c.chips, require_chip=require_chip, cache=cache)

    svc = Service(c, seed)
    print(json.dumps({"graph": svc.graph}), flush=True)
    churn = churn_for(c, svc, seed)
    warm_q = schedule.warmup_query(c.traffic, seed, svc.sources)
    status, _ = post(svc.host, svc.port, "/query", warm_q)
    if status != 200:
        raise RuntimeError(f"warm-up query -> {status}")
    warm_burst, warm_ack = None, None
    if churn is not None:
        warm_burst = churn.burst()
        status, warm_ack = post(svc.host, svc.port, "/update", warm_burst)
        if status != 200:
            raise RuntimeError(f"warm-up update -> {status}")
    stats0 = svc.stats()

    reqs = schedule.queries(c.traffic, seconds, seed, svc.sources)
    if budget_walks is not None:
        for r in reqs:
            r["body"]["budget_walks"] = int(budget_walks)
    if churn is not None:
        reqs += schedule.updates(c.traffic, seconds, churn)
    loads: list[float] = []

    def on_compile(event, *_, **__):
        if event == COMPILE_EVENT:
            loads.append(time.monotonic())

    def on_cache_hit(event, *_, **__):
        if event == CACHE_HIT_EVENT:
            loads.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_cache_hit)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        t0, results = drive(svc, reqs, seconds,
                            workers=int(c.traffic["workers"]),
                            trace_dir=trace_dir)
        setup_s = t0 - t_start
        t_close = t0 + seconds
        in_window_loads = sum(t0 <= t <= t_close for t in loads)
        stats1 = svc.stats()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[: c.chips])
        edges = svc.device_edges() if churn is not None else None
        svc.stop()
        tr = tracereduce.load_xplane(trace_dir) if trace else None
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_cache_hit)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    out = outcomes(reqs, results, t0 + seconds + GRACE_S,
                   n=svc.n, k=int(c.traffic["queries"]["k"]))
    hist0 = stats0["service"]["batch_hist"]
    sent = sorted(r["t_send"] - r["t_sched"] for r in results
                  if r["t_send"] is not None)
    print(json.dumps({"window": {
        "queries": len(out.qlat), "updates": len(out.ulat),
        "failed": out.failed, "compiles_in_window": in_window_loads,
        "regrows": sum(t["regrows"] for t in stats1["tenants"].values()),
        "lateness_ms": {"p50": 1e3 * statistics.median(sent),
                        "p99": 1e3 * sent[int(0.99 * (len(sent) - 1))],
                        "max": 1e3 * sent[-1]} if sent else None,
        "batch_hist": {b: v - hist0.get(b, 0)
                       for b, v in stats1["service"]["batch_hist"].items()
                       if v - hist0.get(b, 0)},
        "memory_peak_bytes": peak}}), flush=True)

    t_ref = time.monotonic()
    checks, checked, readings = correctness(c, svc, seed, out, warm_burst,
                                            warm_ack, edges)
    correct = checked > 0 and all(v <= lim for v, lim in checks.values())
    print(json.dumps({"reference": {"checked_answers": checked,
                                    "seconds": time.monotonic() - t_ref}}),
          flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": c.chips, "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": out.failed, "device": device}
    units = {m["name"]: m["unit"] for m in c.end_to_end + c.per_layer}
    if not trace:
        in_window = sum(a[2]["t_recv"] <= t_close for a in out.answers)
        values = {"setup_s": setup_s, "qps": in_window / seconds,
                  "query_p90_ms": p90(out.qlat), "update_p90_ms": p90(out.ulat)}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": units[m["name"]]}
                             for m in c.end_to_end}
    else:
        lo, hi = tracereduce.window(tr)
        ctx = SimpleNamespace(answers=[a[1] for a in out.answers], trace=tr,
                              window=(lo, hi))
        result["metrics"] = {}
        for m in c.per_layer:
            v = load_module(c.bench_dir, "metrics", m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        device["busy_s"] = tracereduce.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tracereduce.top_ops(tr, lo, hi),
            "idle_gaps": tracereduce.idle_gaps(tr, lo, hi)}
    result["readings"] = readings
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def outcomes(reqs: list, results: list, give_up: float, *, n: int,
             k: int) -> SimpleNamespace:
    """Sort what came back: well-formed answers and acknowledgements (each
    ``(request body, response body, result)``), latencies in ms from the
    scheduled send (a request without a good answer counts as late as the
    wait that gave it up), and the counts of failures."""
    out = SimpleNamespace(answers=[], acks=[], qlat=[], ulat=[], failed=0,
                          unanswered=0, malformed=0)
    for r, res in zip(reqs, results):
        good = res["status"] == 200
        if r["path"] == "/query":
            ok = good and check.well_formed(r["body"], res["body"], n=n, k=k)
            out.malformed += good and not ok
            good, kept, lat = ok, out.answers, out.qlat
        else:
            kept, lat = out.acks, out.ulat
        if good:
            kept.append((r["body"], res["body"], res))
        lat.append(1e3 * ((res["t_recv"] if good else give_up) - res["t_sched"]))
        out.failed += not good
        out.unanswered += res["status"] == 0 or res["status"] >= 500
    return out


def correctness(c, svc: Service, seed: int, out: SimpleNamespace,
                warm_burst, warm_ack, edges) -> tuple[dict, int]:
    """``({name: (number, limit)}, answers checked, every number
    compared)``: the exact checks, and the window's answers against the
    plain reference on the graph
    version each reports (every answer of a static graph; on a churning
    graph, every answer at ``VERSIONS_CHECKED`` or more versions drawn from
    the seed, enough for ``ANSWERS_CHECKED`` answers, and the device's edge
    set against a replay of the
    acknowledged bursts)."""
    guar, n = c.config["guarantee"], svc.n
    checks = {"unanswered": (out.unanswered, 0),
              "malformed": (out.malformed, 0)}
    ref = load_module(c.bench_dir, "configs", c.config["reference"])
    iters = ref.iterations_for(guar["c"], c.config["reference_tol"])

    def rows(src, dst, answers):
        return ref.rows(src, dst, n, [a[0]["node"] for a in answers],
                        c=guar["c"], iterations=iters)

    if warm_ack is None:
        chosen = out.answers
        got = rows(svc.src, svc.dst, chosen)
        truth = {(a[1]["version"], a[0]["node"]): got[a[0]["node"]]
                 for a in chosen}
    else:
        u = c.traffic["updates"]
        checks["acks_bad"] = (sum(ack["applied"] != u["inserts"] + u["deletes"]
                                  for _, ack, _ in out.acks), 0)
        base = int(warm_ack["version"])
        stale = 0
        for _, body, res in out.answers:
            need = max([base] + [int(a[1]["version"]) for a in out.acks
                                 if a[2]["t_recv"] < res["t_send"]])
            stale += body["version"] < need
        checks["stale"] = (stale, 0)
        per_version = Counter(a[1]["version"] for a in out.answers)
        versions = sorted(per_version)
        keep, covered = set(), 0
        for i in schedule.rng_for(seed, 4).permutation(len(versions)):
            if len(keep) >= VERSIONS_CHECKED and covered >= ANSWERS_CHECKED:
                break
            keep.add(versions[i])
            covered += per_version[versions[i]]
        chosen = [a for a in out.answers if a[1]["version"] in keep]
        replay = Replay(svc.src, svc.dst, n)
        replay.apply(warm_burst)
        pending = sorted(((int(ack["version"]), body)
                          for body, ack, _ in out.acks), key=lambda p: p[0])
        truth = {}
        for v in sorted(keep) + [math.inf]:
            while pending and pending[0][0] <= v:
                replay.apply(pending.pop(0)[1])
            if v == math.inf:
                break
            got = rows(*replay.edges(), [a for a in chosen if a[1]["version"] == v])
            truth.update({(v, node): row for node, row in got.items()})
        checks["edge_diff"] = (edge_diff(edges, replay.edges(), n), 0)
    numbers = check.compare([(a[0], a[1]) for a in chosen], truth,
                            walks=int(guar["walks_per_query"]),
                            eps_a=float(guar["eps_a"]))
    for name, limit in c.config["limits"].items():
        checks[name] = (numbers.get(name, float("nan")), limit)
    return checks, len(chosen), numbers


class Replay:
    """The acknowledged operations replayed on a host multiset of edges."""

    def __init__(self, src, dst, n: int):
        self.n = n
        self.edges_ = Counter((np.asarray(src, np.int64) * n
                               + np.asarray(dst, np.int64)).tolist())

    def apply(self, body: dict) -> None:
        for s, d in body["inserts"]:
            self.edges_[s * self.n + d] += 1
        for s, d in body["deletes"]:
            key = s * self.n + d
            if self.edges_[key] > 0:
                self.edges_[key] -= 1

    def edges(self):
        keys = np.fromiter(self.edges_.elements(), np.int64)
        return (keys // self.n).astype(np.int32), (keys % self.n).astype(np.int32)


def edge_diff(got, want, n: int) -> int:
    """Size of the multiset difference between two edge lists."""
    a = Counter((np.asarray(got[0], np.int64) * n + got[1]).tolist())
    b = Counter((np.asarray(want[0], np.int64) * n + want[1]).tolist())
    return sum(((a - b) + (b - a)).values())
