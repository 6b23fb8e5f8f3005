"""Readings for the limits of ``correct``: sound runs and the control.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --sound 1,2,3 --control 4,5,6

Runs the cell once per seed, all in this one process, and prints for each
run one JSON line with its kind, seed, ``correct`` and every number
compared with its limit.  ``--sound`` seeds run the program as the
benchmark does (the lower readings).  ``--control`` seeds run it with the
guarantee broken: every query asks for ``walks_per_query / WALK_CUT``
walks through the program's own anytime budget, so its answers no longer
meet the stated Thm-1 budget (the upper readings).  The benchmark's own
runs never run this.
"""
import argparse
import json
import sys

import harness

WALK_CUT = 16


def _seeds(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    args = ap.parse_args()
    walks = harness.load_cell(args.workload).config["guarantee"][
        "walks_per_query"]
    plan = ([("sound", s, None) for s in _seeds(args.sound)]
            + [("control", s, walks // WALK_CUT) for s in _seeds(args.control)])
    for kind, seed, budget in plan:
        r = harness.run(args.workload, seed, args.seconds, False,
                        budget_walks=budget)
        print(json.dumps({"kind": kind, "seed": seed, "correct": r["correct"],
                          "checks": r["checks"], "readings": r["readings"],
                          "metrics": r["metrics"]}),
              flush=True)


if __name__ == "__main__":
    sys.exit(main())
