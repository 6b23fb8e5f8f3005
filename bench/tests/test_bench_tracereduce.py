"""The reduction from a profiler trace to busy time, module time and idle
gaps, on a hand-made trace and on a small recorded one."""
import gzip
import json
import os
import sys

from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import tracereduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_small.json.gz")


def _made():
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_fused_serve_impl(7)", 100.0, 300.0],
                ["jit_apply_update_batch(3)", 500.0, 50.0],
                ["jit_fused_serve_impl(7)", 700.0, 200.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100.0, 100.0], ["scatter.2", 150.0, 150.0],
                ["fusion.1", 500.0, 50.0], ["fusion.1", 700.0, 200.0]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [[tr.WINDOW_SPAN, 0.0, 1000.0]]},
            {"name": "collector", "events": [
                ["ExecuteHelper", 300.0, 150.0], ["wait", 560.0, 100.0]]}]},
    ]}


def test_union_and_busy():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    t = _made()
    assert tr.window(t) == (0.0, 1000.0)
    assert tr.busy_ns(t, 0.0, 1000.0) == 200.0 + 50.0 + 200.0
    assert tr.busy_ns(t, 0.0, 200.0) == 100.0


def test_module_durations_by_name():
    t = _made()
    assert tr.module_durations_ns(t, "jit_fused_serve_impl") == [300.0, 200.0]
    assert tr.module_durations_ns(t, "jit_apply_update_batch") == [50.0]
    assert tr.module_durations_ns(t, "jit_fused") == []


def test_top_ops_and_idle_gaps_named_by_host_activity():
    t = _made()
    assert tr.top_ops(t, 0.0, 1000.0) == [["fusion.1", 350e-9],
                                           ["scatter.2", 150e-9]]
    assert tr.top_ops(t, 0.0, 200.0) == [["fusion.1", 100e-9],
                                          ["scatter.2", 50e-9]]
    gaps = tr.idle_gaps(t, 0.0, 1000.0)
    assert gaps[0] == ["ExecuteHelper", 200e-9]  # (300, 500)
    assert ["idle: no host event", 100e-9] in gaps  # (0, 100) and (900, 1000)
    assert ["wait", 150e-9] in gaps  # (550, 700)


def test_no_device_plane_reads_nothing():
    t = {"planes": [p for p in _made()["planes"] if p["name"] == "/host:CPU"]}
    assert tr.busy_ns(t, 0.0, 1.0) == 0.0
    assert tr.module_durations_ns(t, "jit_fused_serve_impl") == []
    assert tr.idle_gaps(t, 0.0, 1.0) == []


@pytest.fixture(scope="module")
def recorded():
    """44 ms of a traced ``wikivote-churn`` run on one TPU v5e: the end of
    one fused serve dispatch, then two update bursts' apply programs."""
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_planes_and_lines(recorded):
    (dev,) = tr.device_planes(recorded)
    assert dev["name"] == "/device:TPU:0"
    assert {l["name"] for l in dev["lines"]} >= {tr.OPS_LINE, tr.MODULES_LINE}
    assert tr.host_lines(recorded)


def test_recorded_module_times(recorded):
    lo, hi = tr.window(recorded)
    assert hi - lo == pytest.approx(43.704852e6)
    # two apply programs (the insert-only and the delete bucket of a burst)
    assert tr.module_durations_ns(recorded, "jit_apply_update_batch", lo,
                                  hi) == [166131.0, 1873118.0]
    # the serve dispatch began before the window: it is not counted whole
    assert tr.module_durations_ns(recorded, "jit_fused_serve_impl", lo, hi) == []
    assert tr.module_durations_ns(recorded, "jit_fused_serve_impl") == [
        3794018870.0]


def test_recorded_programs_kept_apart(recorded):
    lo, hi = tr.window(recorded)
    progs = tr.program_durations_ns(recorded, "jit_apply_update_batch", lo, hi)
    assert sorted(progs.values()) == [[166131.0], [1873118.0]]
    assert set(progs) == {"jit_apply_update_batch(6953151957294780304)",
                          "jit_apply_update_batch(10751727943293610182)"}


def test_update_apply_reads_one_burst(recorded):
    """The insert and the delete program of a burst, summed."""
    import harness

    c = harness.load_cell("wikivote-churn")
    read = harness.load_module(c.bench_dir, "metrics", "update_apply_ms").read
    ctx = SimpleNamespace(answers=[], trace=recorded, window=tr.window(recorded))
    assert read(ctx) == pytest.approx(0.166131 + 1.873118)
    # a burst's programs repeated: still the time of one burst
    dev = tr.device_planes(recorded)[0]
    mods = next(l for l in dev["lines"] if l["name"] == tr.MODULES_LINE)
    apply_ = [e for e in mods["events"] if e[0].startswith("jit_apply")]
    more = [[e[0], e[1] + 1.0, e[2] * 1.5] for e in apply_]
    mods2 = dict(mods, events=mods["events"] + more + [
        [apply_[0][0], apply_[0][1] + 2.0, apply_[0][2]]])
    dev2 = dict(dev, lines=[mods2 if l is mods else l for l in dev["lines"]])
    tr2 = {"planes": [dev2 if p is dev else p for p in recorded["planes"]]}
    # insert program: 166131, 249196.5, 166131 -> median 166131;
    # delete program: 1873118, 2809677 -> median 2341397.5
    assert read(SimpleNamespace(answers=[], trace=tr2, window=(-1e30, 1e30))
                ) == pytest.approx((166131.0 + 2341397.5) / 1e6)


def test_recorded_busy_ops_and_gaps(recorded):
    lo, hi = tr.window(recorded)
    busy = tr.busy_ns(recorded, lo, hi)
    assert busy == pytest.approx(21.551179e6)
    ops = tr.top_ops(recorded, lo, hi)
    # the segment-sum push of one probe level over 104,713 edge slots
    assert ops[0][0] == "fusion.185 f32[7116,256] fusion"
    assert sum(s for _, s in ops) <= busy / 1e9 + 1e-12
    gaps = tr.idle_gaps(recorded, lo, hi)
    assert gaps[0] == ["shard_args", pytest.approx(0.011403688)]
    assert "PjitFunction(apply_update_batch)" in {g[0] for g in gaps}
    assert sum(s for _, s in gaps) <= (hi - lo - busy) / 1e9 + 1e-12


def test_op_labels():
    assert tr.op_label(
        "%fusion.177 = f32[34547,256]{1,0:T(8,128)S(1)} fusion(s32[421578]"
        "{0:T(1024)S(1)} %g), kind=kCustom") == "fusion.177 f32[34547,256] fusion"
    assert tr.op_label(
        "%sort.4 = (s32[421578]{0:T(1024)S(1)}, s32[421578]{0:T(1024)S(1)}) "
        "sort(s32[421578]{0:T(1024)S(1)} %c)") == "sort.4 (s32[421578], s32[421578]) sort"
    assert tr.op_label("copy-start.3") == "copy-start.3"
