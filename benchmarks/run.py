"""Benchmark harness entry: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and
writes machine-readable artifacts: ``BENCH_serve.json`` (serving queries/sec
for the serial vs fused-batched drain) when the serve suite runs,
``BENCH_dynamic.json`` (incremental vs rebuild update throughput and
update->queryable latency) when the dynamic suite runs, and
``BENCH_abserror.json`` (the adaptive-controller epsilon sweep: walks used,
oracle max-abs-error vs certified bound, precision@10, walks saved vs the
flat budget) when the abserror suite runs — each also carrying every
emitted row.  ``--full`` runs paper-scale sweeps; default (``--quick``) is
the CPU-quick profile.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# allow both `python -m benchmarks.run` and `python benchmarks/run.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CPU-quick profile (the default; negates --full)")
    ap.add_argument("--only", default=None,
                    help="comma list: serve,service,abserror,topk,large,"
                         "dynamic,stream")
    ap.add_argument("--backend", choices=("local", "sharded"), default="local",
                    help="forwarded to suites that take it (serve, dynamic, "
                         "service, stream): 'sharded' adds the mesh-backend "
                         "comparison rows")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path; by default "
                         "BENCH_serve.json is written iff the serve suite ran "
                         "(so other suites never clobber the serve artifact)")
    args = ap.parse_args()
    quick = not args.full or args.quick

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_abserror,
        bench_dynamic,
        bench_large,
        bench_serve,
        bench_service,
        bench_stream,
        bench_topk,
    )
    from benchmarks.common import RESULTS, ROWS, write_json

    suites = dict(
        serve=bench_serve.run,
        service=bench_service.run,
        abserror=bench_abserror.run,
        topk=bench_topk.run,
        large=bench_large.run,
        dynamic=bench_dynamic.run,
        stream=bench_stream.run,
    )
    takes_backend = {"serve", "dynamic", "service", "stream"}  # mesh legs
    # suites that must fill RESULTS[name]; abserror is structured too — it
    # used to print CSV rows and silently drop its metrics, so the
    # accuracy-gate job had nothing machine-readable to enforce
    structured = {"serve", "dynamic", "abserror", "service", "stream"}
    chosen = args.only.split(",") if args.only else list(suites)
    unknown = [name for name in chosen if name not in suites]
    if unknown:
        ap.error(f"unknown suite(s): {', '.join(unknown)} "
                 f"(have: {', '.join(suites)})")
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in chosen:
        print(f"# suite: {name}", file=sys.stderr)
        rows_before = len(ROWS)
        if name in takes_backend:
            suites[name](quick=quick, backend=args.backend)
        else:
            suites[name](quick=quick)
        # fail LOUDLY when a requested suite produced nothing: a silently
        # empty artifact reads as "benchmark ran" to every downstream
        # consumer (CI gates, acceptance checks) when it did not
        if len(ROWS) == rows_before:
            sys.exit(f"suite '{name}' was requested but emitted no rows")
        if name in structured and name not in RESULTS:
            sys.exit(f"suite '{name}' was requested but exported no "
                     f"RESULTS['{name}'] row for its JSON artifact")
        if (name in takes_backend and args.backend == "sharded"
                and "backend" not in RESULTS[name]
                and "sharded" not in RESULTS[name]):
            sys.exit(f"suite '{name}' ran with --backend sharded but "
                     "exported no sharded comparison row")
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json:
        write_json(args.json, quick=quick, suites=chosen)
    else:
        # one artifact per acceptance consumer, written iff its suite ran
        # (so other suites never clobber an existing artifact)
        if "serve" in chosen or "service" in chosen:
            write_json("BENCH_serve.json", quick=quick, suites=chosen)
        if "dynamic" in chosen:
            write_json("BENCH_dynamic.json", quick=quick, suites=chosen)
        if "abserror" in chosen:
            write_json("BENCH_abserror.json", quick=quick, suites=chosen)
        if "stream" in chosen:
            write_json("BENCH_stream.json", quick=quick, suites=chosen)


if __name__ == "__main__":
    main()
