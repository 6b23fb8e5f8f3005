"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, in one process that holds the chip(s);
the load generator is a child process on the standard library.  Earlier
lines of standard output describe the realized graph, the window
(compilations, regrows, generator lateness, batch sizes, peak memory) and
the reference check; the last line is the result as one JSON object.  The
numbers that decide ``correct`` are also the last lines of standard error,
each beside its limit.  Exits non-zero, printing no result, when JAX finds
no accelerator or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
