"""The served path's host spans and its probe-level counter.

Spans (``repro.utils.spans``) are TraceAnnotations: under
``jax.profiler`` they land on the host plane of the same trace as the
device's work, which is what the benchmark's trace readers rely on.  The
counter is one more output of the fused serve step: it must count within
the loop's bound, be shared by every answer of a dispatch, and leave the
answers bitwise as they are without it.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api.handle import GraphHandle
from repro.api.session import SimRankSession
from repro.api.spec import QuerySpec
from repro.core.multisource import (
    _fused_serve,
    fused_serve_impl,
    lane_max_steps,
)
from repro.core.params import make_params
from repro.serving import ServiceConfig, SimRankService
from repro.serving.protocol import QueryRequest
from repro.utils import spans


@pytest.fixture(scope="module")
def handle():
    rng = np.random.default_rng(11)
    n = 40
    return GraphHandle.from_edges(
        rng.integers(0, n, 260), rng.integers(0, n, 260), n
    )


def _host_events(log_dir: str) -> list[tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every host-plane event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                )
    return out


def test_served_path_spans_on_the_host_plane(handle, tmp_path):
    svc = SimRankService(handle, config=ServiceConfig(
        batch_window_ms=20.0, max_batch_q=4, default_budget_walks=64,
    ))
    answers = []
    try:
        svc.serve_request(QueryRequest(node=1, k=5, seed=1))  # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            time.sleep(0.6)  # the collector idles with nothing pending
            threads = [
                threading.Thread(target=lambda u=u: answers.append(
                    svc.serve_request(QueryRequest(node=u, k=5, seed=u))))
                for u in (2, 3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            svc.apply_update(inserts=np.array([[1, 2]]),
                             deletes=np.array([[3, 4]]))
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close()
    assert [status for status, _ in answers] == [200, 200]
    events = _host_events(str(tmp_path))
    seen = {name for name, _, _ in events}
    assert set(spans.NAMES) <= seen
    # every fetch lies inside a dispatch
    dispatches = [(a, b) for name, a, b in events if name == spans.DISPATCH]
    fetches = [(a, b) for name, a, b in events
               if name == spans.DISPATCH_FETCH]
    assert fetches and all(
        any(a <= c and d <= b for a, b in dispatches) for c, d in fetches
    )
    stats = svc.stats_snapshot()
    assert stats["service"]["update_lock_wait_s"] >= 0.0
    levels = [p["probe_levels"] for _, p in answers]
    assert stats["tenants"]["default"]["probe_levels"] >= sum(set(levels))


def test_span_keeps_its_duration():
    with spans.span(spans.DISPATCH) as s:
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 5.0
    lock = threading.Lock()
    with spans.locked(lock, spans.LOCK_UPDATE) as waited:
        assert lock.locked() and waited >= 0.0
    assert not lock.locked()


def test_probe_levels_bounded_and_shared_by_a_dispatch(handle):
    sess = SimRankSession(handle, batch_q=4, eps_a=0.2)
    n_r = 96
    for u in (1, 2, 3):
        sess.submit(QuerySpec(kind="topk", node=u, k=5, budget_walks=n_r))
    envs = sess.drain()
    levels = {e.probe_levels for e in envs}
    assert len(levels) == 1
    (lv,) = levels
    assert isinstance(lv, int)
    assert 1 <= lv <= lane_max_steps(n_r, sess.params.max_len) + 1
    assert sess.stats.probe_levels == lv


def test_adaptive_levels_sum_over_rounds(handle):
    sess = SimRankSession(handle, batch_q=2, eps_a=0.2, initial_budget=16)
    env = sess.query(QuerySpec(kind="topk", node=3, k=5, epsilon=1e-4,
                               budget_walks=256))
    assert env.rounds > 1
    assert env.probe_levels == sess.stats.probe_levels > 0


def test_push_path_rides_with_the_levels(handle):
    """Each fused answer names the push its levels ran (the XLA COO level
    off the TPU), in the envelope, its wire form and the session stats; a
    one-shot legacy query counts no levels and names none."""
    from repro.serving.protocol import envelope_to_wire

    sess = SimRankSession(handle, batch_q=2, eps_a=0.2)
    assert sess.stats.push_path is None
    sess.submit(QuerySpec(kind="topk", node=1, k=5, budget_walks=64))
    (env,) = sess.drain()
    assert env.push_path == sess.stats.push_path == "coo_xla"
    assert envelope_to_wire(env)["push_path"] == "coo_xla"
    adaptive = sess.query(QuerySpec(kind="topk", node=2, k=5, epsilon=0.5,
                                    budget_walks=64))
    assert adaptive.push_path == "coo_xla"
    one = sess.query(QuerySpec(kind="topk", node=3, k=5, budget_walks=64))
    assert one.probe_levels is None and one.push_path is None
    assert "push_path" not in envelope_to_wire(one)
    assert sess.stats.push_path == "coo_xla"


@pytest.mark.parametrize("top_k", [0, 5])
def test_counter_leaves_answers_bitwise_unchanged(handle, top_k):
    """The step with its level count against the same step compiled
    without that output."""
    p = make_params(handle.n, c=0.6, eps_a=0.2, delta=0.01)
    q = 4
    keys = jax.random.split(jax.random.key(5), q)
    us = jnp.arange(1, q + 1, dtype=jnp.int32)
    static = dict(
        n_r=128, lanes_q=64 // q, max_len=p.max_len, sqrt_c=p.sqrt_c,
        eps_p=p.eps_p, eps_t=p.eps_t, truncation_shift=p.truncation_shift,
        top_k=top_k,
    )
    g, eg = handle.g, handle.eg

    def acc():
        return jnp.zeros((q, handle.n), jnp.float32)

    live = jnp.int32(q)
    _, est, idx, vals, levels, _, _ = _fused_serve(
        keys, g, eg, us, acc(), live, **static
    )
    without = jax.jit(lambda *a: fused_serve_impl(*a, **static)[:4])
    _, est0, idx0, vals0 = without(keys, g, eg, us, acc(), live)
    if top_k:
        assert np.array_equal(np.asarray(idx), np.asarray(idx0))
        assert np.array_equal(np.asarray(vals), np.asarray(vals0))
    assert np.array_equal(np.asarray(est), np.asarray(est0))
    assert int(levels) >= 1
