"""Find a cell's knee: the highest query rate whose backlog does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --fractions 0.7,0.85,1.0,1.15

One process builds the cell's service once and warms it, times a few
single-query dispatches (a dispatch serves up to ``max_batch_q`` queries
in the same time, so ``max_batch_q / dispatch`` estimates the capacity),
then offers the cell's traffic at each fraction of that estimate (or at
``--rates``) for ``--seconds`` each, updates included.  For each rate it
prints one JSON line: answered share, p50 and p90 latency, and the growth
of latency across the window (median of the last third of arrivals over
the first third; a backlog that grows reads well above 1).  The knee is
read from these lines by hand and written into the traffic file.
"""
import argparse
import json
import statistics
import sys
import time

import harness
import schedule


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fractions", default="0.7,0.85,1.0,1.15")
    ap.add_argument("--rates", default=None)
    args = ap.parse_args()

    c = harness.load_cell(args.workload)
    harness.start_jax(c.chips, require_chip=True, cache=True)
    t = time.monotonic()
    svc = harness.Service(c, args.seed)
    churn = harness.churn_for(c, svc, args.seed)
    warm = schedule.warmup_query(c.traffic, args.seed, svc.sources)
    harness.post(svc.host, svc.port, "/query", warm)
    if churn is not None:
        harness.post(svc.host, svc.port, "/update", churn.burst())
    times = []
    for i in range(3):
        t1 = time.monotonic()
        status, _ = harness.post(svc.host, svc.port, "/query",
                                 dict(warm, seed=warm["seed"] + 1 + i))
        times.append(time.monotonic() - t1)
    dispatch_s = statistics.median(times)
    q = c.config["service"]["max_batch_q"]
    print(json.dumps({"setup_s": time.monotonic() - t, "dispatch_s": times,
                      "capacity_estimate_qps": q / dispatch_s}), flush=True)
    rates = ([float(r) for r in args.rates.split(",")] if args.rates else
             [f * q / dispatch_s for f in map(float, args.fractions.split(","))])
    for i, rate in enumerate(rates):
        seed = args.seed + 1000 * (i + 1)
        reqs = schedule.queries(c.traffic, args.seconds, seed, svc.sources,
                                rate=rate)
        if churn is not None:
            reqs += schedule.updates(c.traffic, args.seconds, churn)
        t0, results = harness.drive(svc, reqs, args.seconds,
                                    workers=int(c.traffic["workers"]))
        lat = [(r["t_sched"], (r["t_recv"] - r["t_sched"]) if r["status"] == 200
                else float("inf"), rq["path"])
               for rq, r in zip(reqs, results)]
        ql = [x[1] for x in sorted(lat) if x[2] == "/query"]
        ul = [x[1] for x in lat if x[2] == "/update"]
        third = max(1, len(ql) // 3)
        print(json.dumps({
            "rate_qps": rate, "queries": len(ql),
            "answered_share": sum(v != float("inf") for v in ql) / max(1, len(ql)),
            "p50_ms": 1e3 * statistics.median(ql) if ql else None,
            "p90_ms": 1e3 * harness.p90(ql) if ql else None,
            "growth": (statistics.median(ql[-third:]) / statistics.median(ql[:third])
                       if ql else None),
            "update_p90_ms": 1e3 * harness.p90(ul) if ul else None,
            "batch_hist": svc.stats()["service"]["batch_hist"],
        }), flush=True)
    svc.stop()


if __name__ == "__main__":
    sys.exit(main())
