"""The readers of the program's spans and level counter, on hand-made
traces: each gives the value the trace was built to hold, and nothing
where its spans or its counter are missing (as in a program without
them)."""
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spanreduce as sr  # noqa: E402
import tracereduce as tr  # noqa: E402

NAMES = ("probe_levels", "probe_level_ms", "host_stall_pct",
         "update_lock_wait_ms", "update_host_ms")
MS = 1e6  # ns


def _reader(name):
    c = harness.load_cell("wikivote-churn")
    return harness.load_module(c.bench_dir, "metrics", name).read


def _trace(spans=True):
    """A 100 ms window: the collector idles 0-20 ms, then two dispatches
    of 30 ms (device 22-52 and 60-90 ms), two updates between them."""
    host = [["bench:traced_window", 0.0, 100 * MS]]
    collector = []
    if spans:
        collector = [
            ["probesim:collector.idle", -5 * MS, 10 * MS],  # cut at 0
            ["probesim:collector.idle", 5 * MS, 15 * MS],
            ["probesim:collector.window", 20 * MS, 1 * MS],
            ["probesim:dispatch", 21 * MS, 32 * MS],
            ["probesim:dispatch.fetch", 22 * MS, 30.5 * MS],
            ["probesim:dispatch", 58 * MS, 33 * MS],
            ["probesim:collector.idle", 92 * MS, 20 * MS],  # ends past hi
        ]
        updater = [
            ["probesim:lock.update", 30 * MS, 23 * MS],
            ["probesim:update", 53 * MS, 2 * MS],
            ["probesim:lock.update", 56 * MS, 1 * MS],
            ["probesim:update", 57 * MS, 4 * MS],
        ]
    else:
        updater = []
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_fused_serve_impl(3)", 22 * MS, 30 * MS],
                ["jit_apply_update_batch(1)", 54 * MS, 1 * MS],
                ["jit_fused_serve_impl(3)", 60 * MS, 30 * MS]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 22 * MS, 30 * MS],
                ["fusion.2", 54 * MS, 1 * MS],
                ["fusion.1", 60 * MS, 30 * MS]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": host},
            {"name": "collector", "events": collector},
            {"name": "handler", "events": updater}]},
    ]}


def _answers(levels=True):
    """Three answers of the first dispatch and one of the second."""
    out = []
    for version, lat, lv, count in ((4, 0.032, 1200, 3), (6, 0.033, 1800, 1)):
        for _ in range(count):
            a = {"version": version, "latency_s": lat}
            if levels:
                a["probe_levels"] = lv
            out.append(a)
    return out


def _ctx(trace, answers):
    return SimpleNamespace(answers=answers, trace=trace,
                           window=tr.window(trace))


def test_readers_give_the_built_values():
    ctx = _ctx(_trace(), _answers())
    assert _reader("probe_levels")(ctx) == 1500.0
    # 60 ms of serve programs over 3000 levels
    assert _reader("probe_level_ms")(ctx) == pytest.approx(60.0 / 3000)
    # idle 0-22, 52-54, 55-60, 90-100 ms; idle spans cover 0-20 and 92-100
    assert _reader("host_stall_pct")(ctx) == pytest.approx(
        100.0 * (2 + 2 + 5 + 2) / 100)
    assert _reader("update_lock_wait_ms")(ctx) == pytest.approx(12.0)
    assert _reader("update_host_ms")(ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NAMES)
def test_readers_read_nothing_without_spans_or_counter(name):
    read = _reader(name)
    assert read(_ctx(_trace(spans=False), _answers(levels=False))) is None
    assert read(SimpleNamespace(answers=[], trace=None, window=None)) is None


def test_level_time_needs_one_count_per_dispatch():
    ctx = _ctx(_trace(), _answers())
    assert _reader("probe_level_ms")(ctx) is not None
    ctx.answers = ctx.answers[:3]  # the second dispatch's answer lost
    assert _reader("probe_level_ms")(ctx) is None
    assert _reader("probe_levels")(ctx) == 1200.0


def test_span_helpers():
    t = _trace()
    lo, hi = tr.window(t)
    assert [e[1] for e in sr.span_events(t, "probesim:collector.idle")] == [
        -5 * MS, 5 * MS, 92 * MS]
    assert [e[1] for e in sr.span_events(t, "probesim:collector.idle",
                                         lo, hi)] == [5 * MS]
    assert sr.device_idle(t, lo, hi) == [(0.0, 22 * MS), (52 * MS, 54 * MS),
                                         (55 * MS, 60 * MS), (90 * MS, hi)]
    assert sr.uncovered_ns([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == 12
    assert sr.uncovered_ns([(0, 10)], []) == 10
    assert sr.dispatch_levels(_answers()) == {(4, 0.032): 1200,
                                             (6, 0.033): 1800}
