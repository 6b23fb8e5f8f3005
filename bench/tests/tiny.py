"""A tiny copy of the benchmark for CPU tests: the committed cells with
their graphs cut to a few hundred nodes and their windows to seconds.
Everything else (limits, service settings, the reference) is as committed.
"""
import json
import math
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def make(root, *, n=300, m=2400, rate=None, workers=16, period_s=0.5):
    """Copy BENCHMARK.json and the benchmark's files under ``root``, with
    every configuration's graph cut to ``n`` nodes and ``m`` edges."""
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        path = os.path.join(root, c["file"])
        cfg = json.load(open(path))
        g = cfg["guarantee"]
        if cfg["graph"].get("in_edge_nodes"):
            cfg["graph"]["in_edge_nodes"] = max(2, round(
                cfg["graph"]["in_edge_nodes"] * n / cfg["graph"]["n"]))
        cfg["graph"].update(n=n, m=m)
        g["walks_per_query"] = math.ceil(
            3 * g["c"] / (g["eps_a"] / 2) ** 2 * math.log(n / g["delta"]))
        json.dump(cfg, open(path, "w"))
    for cell in spec["workloads"]:
        path = os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
        t = json.load(open(path))
        if rate is not None:
            t["queries"]["rate_per_s"] = rate
        t["workers"] = workers
        if t.get("updates"):
            t["updates"]["period_s"] = period_s
        json.dump(t, open(path, "w"))
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return str(root)
