"""The lane-probe level: the CSR Pallas kernel (interpret mode) against
the XLA COO level, the fused serve and epoch steps on the CSR path, the
walk-sampling split, and the mesh lane probes on 8 fake devices
(subprocess: XLA_FLAGS must precede jax init)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Walks from pre-drawn uniforms: row subsets of one uniform draw are
# bitwise identical to the full-pool walk
# ---------------------------------------------------------------------------


def test_walks_from_uniform_subsets_bitwise(small_powerlaw, key):
    from repro.core.walks import (
        sample_walks, walk_uniforms, walks_from_uniforms
    )

    eg = small_powerlaw["eg"]
    full = sample_walks(key, eg, 3, n_r=64, max_len=10, sqrt_c=0.77)
    cont, pick = walk_uniforms(key, n_r=64, max_len=10, sqrt_c=0.77)
    head = walks_from_uniforms(eg, 3, cont[:16], pick[:16])
    tail = walks_from_uniforms(eg, 3, cont[16:], pick[16:])
    np.testing.assert_array_equal(
        np.asarray(full), np.vstack([np.asarray(head), np.asarray(tail)])
    )


# ---------------------------------------------------------------------------
# Sharded mesh paths (subprocess: 8 fake host devices)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.api import GraphHandle, QuerySpec, SimRankSession
from repro.api.backend import ShardedBackend
from repro.core import make_params
from repro.graph import powerlaw_graph

src, dst, n = powerlaw_graph(120, 900, seed=5)
in_deg = np.bincount(dst, minlength=n)
h = GraphHandle.from_edges(src, dst, n, capacity=len(src) + 256,
                           k_max=int(in_deg.max()) + 8)
p = make_params(n, c=0.6, eps_a=0.2, delta=0.01)
nodes = [int(u) for u in np.where(in_deg > 0)[0][:3]]
kb = jnp.stack([jax.random.key(200 + i) for i in range(3)])

# bf16 frontier wire vs the fp32 wire on the spmd probe
sh = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512)
a, _, _, _ = sh.serve_batch("single_source", nodes, kb, n_r=512)
bf = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                    frontier_dtype="bfloat16")
c, _, _, _ = bf.serve_batch("single_source", nodes, kb, n_r=512)
assert np.abs(a - c).max() < 1e-3, np.abs(a - c).max()
print("SPMD_WIRE_OK")

# the ring probe: same walks, same lane schedule as spmd
ring = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                      probe="ring")
e, _, _, _ = ring.serve_batch("single_source", nodes, kb, n_r=512)
assert np.abs(a - e).max() < 1e-5, np.abs(a - e).max()
print("RING_OK")

# top-k rides the same probe
_, ix, _, _ = sh.serve_batch("topk", nodes, kb, k=5, n_r=512)
_, ir, _, _ = ring.serve_batch("topk", nodes, kb, k=5, n_r=512)
assert all(len(set(ix[i].tolist()) & set(ir[i].tolist())) >= 4
           for i in range(3))

# fused mesh epoch: every insert applied, answers on the new graph
rng = np.random.default_rng(3)
ins = (rng.integers(0, n, 8).astype(np.int32),
       rng.integers(0, n, 8).astype(np.int32))
ekey = jax.random.key(55)
qs = lambda: [QuerySpec(kind="single_source", node=u,
                        key=jax.random.fold_in(ekey, u))
              for u in nodes[:2]]
s1 = SimRankSession(h, seed=0, top_k=5, batch_q=2, update_batch=16,
                    walk_chunk=256, backend="sharded", shards=4)
e1 = s1.epoch(inserts=ins, queries=qs(), budget_walks=256)
assert e1.updates_applied == 8
s2 = SimRankSession(h, seed=0, top_k=5, batch_q=2, update_batch=16,
                    walk_chunk=256)
e2 = s2.epoch(inserts=ins, queries=qs(), budget_walks=256)
g1 = np.stack([r.scores for r in e1.results])
g2 = np.stack([r.scores for r in e2.results])
assert np.abs(g1 - g2).max() < 1e-4, np.abs(g1 - g2).max()
print("EPOCH_OK")
"""


def test_sharded_wire_ring_and_epoch_on_fake_mesh():
    """The mesh lane probes on 8 fake XLA host devices: the bf16 frontier
    wire against the fp32 wire (1e-3), spmd against ring (1e-5), top-k
    overlap, and a fused mesh epoch that applies all 8 inserts and
    matches the local epoch (1e-4)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SPMD_WIRE_OK" in out.stdout
    assert "RING_OK" in out.stdout
    assert "EPOCH_OK" in out.stdout


# ---------------------------------------------------------------------------
# CSR level (the default path's push on the TPU) vs the XLA COO level
# ---------------------------------------------------------------------------


# (n, m, W) of each case; the default is (90, 600, 40)
_CSR_SHAPES = {
    "lanes_256": (90, 600, 256),  # the bench's width: no lane padding
    "tiny": (7, 20, 40),
    "many_blocks": (600, 4000, 40),  # ten row blocks of 64
}


def _csr_case(rng, case):
    """A COO graph and one level's lane state for a CSR parity case."""
    from repro.graph.structs import graph_from_edges

    n, m, w = _CSR_SHAPES.get(case, (90, 600, 40))
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n // 2, m)  # rows n/2 .. n-1 take no in-edges
    cap = m
    if case == "padding":
        cap = m + 77  # padding edges (dst = n) sort past the live rows
    if case == "hub":
        dst[:300] = 5  # a row longer than two id chunks of 128
    if case == "chunk_aligned":
        dst[:512] = np.repeat(np.arange(4), 128)  # rows 0-3: one chunk each
        dst[512:] = rng.integers(4, n // 2, m - 512)
    g = graph_from_edges(src, dst, n, capacity=cap)
    scores = rng.random((n + 1, w)) * (rng.random((n + 1, w)) < 0.4)
    scores[n] = 0.0
    total = rng.random((n + 1, w))
    total[n] = 0.0
    fin = rng.random(w) < 0.3
    u_p = np.where(rng.random(w) < 0.6, rng.integers(0, n, w), n)
    u_prev = np.where(rng.random(w) < 0.6, rng.integers(0, n, w), n)
    if case == "dead_fin":
        fin[: w // 2] = True  # depositing columns; half of them dead
        u_p[: w // 4] = n
        scores[:, w // 4: w // 2] = 0.0
    if case == "sentinels":
        u_p[:] = n
        u_prev[:] = n
    if case == "one_active":  # the tail of a draining batch
        fin[:] = True
        fin[4] = False
    if case == "all_dead":  # every column deposits, none injects
        fin[:] = True
        u_p[:] = n
    thr = rng.random(w) * 0.1
    f32, i32 = jnp.float32, jnp.int32
    return g, (jnp.asarray(scores, f32), jnp.asarray(total, f32),
               jnp.asarray(fin), jnp.asarray(u_p, i32),
               jnp.asarray(u_prev, i32), jnp.asarray(thr, f32))


@pytest.mark.parametrize("prune", [False, True])
@pytest.mark.parametrize(
    "case", ["padding", "hub", "empty_rows", "dead_fin", "sentinels",
             "lanes_256", "tiny", "many_blocks", "one_active", "all_dead",
             "chunk_aligned"]
)
def test_csr_level_matches_coo_level(rng, case, prune):
    """The CSR kernel (interpret mode) against the XLA COO level at 1e-5:
    padding edges, a hub row over several id chunks, rows with no
    in-edges, dead and depositing columns, sentinel u_p/u_prev, W = 256
    (no lane padding), a 7-node graph, ten row blocks, one live column
    among finished ones, every column finished, and rows whose edges
    fill whole id chunks."""
    from repro.core.multisource import xla_level
    from repro.kernels.lane_probe.ops import (
        csr_layout, csr_push_view, lane_probe_csr_level,
    )

    g, (scores, total, fin, u_p, u_prev, thr) = _csr_case(rng, case)
    n, w = g.n, scores.shape[1]
    sqrt_c = 0.6 ** 0.5
    ref_s, ref_t = xla_level(
        g, scores, total, fin, u_p, u_prev, thr,
        cols=jnp.arange(w), sqrt_c=sqrt_c, prune=prune,
    )
    rows, _ = csr_layout(n, block_rows=64)
    view = csr_push_view(g, g.inv_in_deg * sqrt_c, rows=rows, chunk=128)
    grow = ((0, rows - n - 1), (0, 0))
    out, tot = lane_probe_csr_level(
        view, jnp.pad(scores, grow), jnp.pad(total, grow),
        fin, u_p, u_prev, thr, prune=prune, block_rows=64, chunk=128,
    )
    out, tot = np.asarray(out), np.asarray(tot)
    np.testing.assert_allclose(out[: n + 1], ref_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tot[: n + 1], ref_t)
    assert np.all(out[n:] == 0.0)  # the dump row and block padding
    if case == "all_dead":  # nothing pushes; every column deposits
        assert np.all(out == 0.0)
        np.testing.assert_array_equal(
            tot[: n + 1], np.asarray(total) + np.asarray(scores)
        )
        return
    assert np.abs(out).sum() > 0  # a non-degenerate level
    if case == "one_active":
        assert np.abs(out[:, 4]).sum() > 0
        # finished columns send only their injections
        dead = np.delete(np.arange(w), 4)
        inj = np.delete(np.asarray(u_p), 4) < n
        assert np.all((np.abs(out[:, dead]).sum(axis=0) > 0) == inj)


def _serve_and_epoch(d, key, case):
    """(serve before, epoch, serve after) answers on small_powerlaw and
    the serves' push paths: the epoch inserts into spare COO slots and
    deletes live edges.  ``case``: a full single-source batch, a partial
    one (2 live slots of 4) or a full top-k batch."""
    from repro.api import GraphHandle, LocalBackend
    from repro.core import make_params
    from repro.graph.dynamic import make_update_batch

    p = make_params(d["n"], c=0.6, eps_a=0.2, delta=0.01)
    h = GraphHandle.from_edges(d["src"], d["dst"], d["n"],
                               capacity=len(d["src"]) + 64)
    be = LocalBackend(h, params=p, walk_chunk=128)
    q, live = (4, 2) if case == "partial" else (2, None)
    kind, k = ("topk", 5) if case == "topk" else ("single_source", 0)
    keys = jax.random.split(key, q)
    us = [int(u) for u in np.argsort(-np.bincount(d["dst"]))[:q]]

    def serve():
        est, idx, vals, _ = be.serve_batch(kind, us, keys, k=k, n_r=128,
                                           live=live)
        return (idx, vals) if k else (est[:live],)

    before = serve()
    paths = [be.push_path]
    rng = np.random.default_rng(7)
    dels = rng.choice(len(d["src"]), 12, replace=False)
    src = np.concatenate([rng.integers(0, d["n"], 12), d["src"][dels]])
    dst = np.concatenate([rng.integers(0, d["n"], 12), d["dst"][dels]])
    batch = make_update_batch(src, dst, np.arange(24) < 12, batch_size=32,
                              n=d["n"])
    applied, est, idx, vals = be.epoch_batch(batch, us, keys, n_r=128,
                                             top_k=k)
    assert np.asarray(applied)[12:24].all()  # every delete found its edge
    epoch = (idx, vals) if k else (est,)
    after = serve()
    paths.append(be.push_path)
    return [before, epoch, after], paths


@pytest.mark.parametrize("case", ["full", "partial", "topk"])
def test_csr_serve_and_epoch_match_xla(small_powerlaw, key, monkeypatch,
                                       case):
    """The fused serve and epoch steps on the CSR path (steered onto it,
    as on a TPU; interpret mode here) match the XLA path at 1e-5, before
    and after an epoch that inserts and deletes: each dispatch's CSR view
    follows the graph version it serves.  A partial batch runs the
    runtime lane owners; a top-k batch returns the same nodes."""
    from repro.core import multisource

    real = multisource.push_path

    def csr(g, width):
        path = real(g, width)
        return "csr_kernel" if path == "coo_xla" else path

    jax.clear_caches()  # compiled steps carry the path they traced
    try:
        xla, xla_paths = _serve_and_epoch(small_powerlaw, key, case)
        monkeypatch.setattr(multisource, "push_path", csr)
        jax.clear_caches()
        got, got_paths = _serve_and_epoch(small_powerlaw, key, case)
    finally:
        jax.clear_caches()
    assert xla_paths == ["coo_xla"] * 2  # the CPU's default
    assert got_paths == ["csr_kernel"] * 2
    assert not np.array_equal(xla[0][-1], xla[2][-1])  # the update moved
    for a, b in zip(xla, got):
        if case == "topk":
            np.testing.assert_array_equal(b[0], a[0])
        np.testing.assert_allclose(b[-1], a[-1], rtol=1e-5, atol=1e-6)
