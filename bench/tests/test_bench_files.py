"""Cells, configurations, traffic mixes and metric readers are found by
name: a later change adds files and BENCHMARK.json entries, no code."""
import json
import os
import shutil
import sys
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def _copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return json.loads((tmp_path / "BENCHMARK.json").read_text())


def test_every_committed_cell_loads():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in spec["workloads"]:
        c = harness.load_cell(cell["name"])
        assert c.config["name"] == cell["config"]
        assert c.traffic["queries"]["rate_per_s"] > 0
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert callable(harness.load_module(c.bench_dir, "metrics", m["name"]).read)


def test_added_files_are_found_without_code_changes(tmp_path):
    spec = _copy(tmp_path)
    cfg = json.loads((tmp_path / "bench/configs/wiki-vote.json").read_text())
    cfg["name"] = "wiki-vote-small"
    cfg["graph"].update(n=500, m=4000)
    (tmp_path / "bench/configs/wiki-vote-small.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/slow-topk.json").write_text(json.dumps(
        {"queries": {"kind": "topk", "k": 5, "rate_per_s": 0.5},
         "updates": None, "workers": 4}))
    (tmp_path / "bench/metrics/answers_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.answers)) or None\n")
    spec["configs"].append({"name": "wiki-vote-small", "source": "x",
                            "file": "bench/configs/wiki-vote-small.json",
                            "reduced": ["n", "m"], "why": "test"})
    spec["workloads"].append({"name": "small-slow", "config": "wiki-vote-small",
                              "traffic": "slow-topk", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "answers_seen", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "service front end",
                              "moves": "query_p90_ms",
                              "workloads": ["small-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = harness.load_cell("small-slow", root=str(tmp_path))
    assert c.config["graph"]["n"] == 500
    assert c.traffic["queries"]["rate_per_s"] == 0.5
    assert "update_p90_ms" not in {m["name"] for m in c.end_to_end}
    names = [m["name"] for m in c.per_layer]
    assert "answers_seen" in names and "update_apply_ms" not in names
    read = harness.load_module(c.bench_dir, "metrics", "answers_seen").read
    assert read(SimpleNamespace(answers=[{}, {}])) == 2.0
    assert read(SimpleNamespace(answers=[])) is None
    ref = harness.load_module(c.bench_dir, "configs", c.config["reference"])
    assert ref.iterations_for(0.6, 1e-5) == 22


def test_readers_return_nothing_without_their_source():
    c = harness.load_cell("wikivote-churn")
    empty = SimpleNamespace(answers=[], trace=None, window=None)
    for m in c.per_layer:
        assert harness.load_module(c.bench_dir, "metrics", m["name"]).read(empty) is None
