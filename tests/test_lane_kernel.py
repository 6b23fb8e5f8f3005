"""Fused lane-probe level kernel (PR 10): op vs jnp oracle bitwise in fp32
(interpret mode), bf16 storage parity, edge-case shapes, the
walk-sampling split, end-to-end local serve parity, and the sharded
use_kernel=True mesh paths (subprocess: XLA_FLAGS must precede jax init)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.lane_probe.ops import lane_probe_level
from repro.kernels.lane_probe.ref import lane_probe_level_ref


def _random_level(rng, *, n=50, k=6, w=24, t=None, dtype=jnp.float32):
    """A random compacted-lane level problem: some finished columns, some
    injections, some sentinel neighbors (the padded ELL slots)."""
    t = (n + 1) if t is None else t
    nbrs = rng.integers(0, n + 1, (n, k)).astype(np.int32)  # n == sentinel
    weights = rng.random(n).astype(np.float32)
    table = rng.random((t, w)).astype(np.float32)
    dep = rng.random((n, w)).astype(np.float32)
    total = rng.random((n, w)).astype(np.float32)
    fin = rng.random(w) < 0.4
    u_p = np.where(rng.random(w) < 0.5,
                   rng.integers(0, n, w), n).astype(np.int32)
    u_prev = np.where(rng.random(w) < 0.5,
                      rng.integers(0, n, w), n).astype(np.int32)
    thr = (rng.random(w) * 0.3).astype(np.float32)
    args = [jnp.asarray(a) for a in (nbrs, weights, table, dep, total)]
    args = [a.astype(dtype) if a.dtype == jnp.float32 and i >= 2 else a
            for i, a in enumerate(args)]
    return (*args, jnp.asarray(fin), jnp.asarray(u_p), jnp.asarray(u_prev),
            jnp.asarray(thr))


def _check_bitwise(args, *, row0=0, tab0=0, n_live, prune):
    out, tot = lane_probe_level(*args, row0=row0, tab0=tab0, n_live=n_live,
                                prune=prune)
    ref_out, ref_tot = lane_probe_level_ref(
        *args, row0=row0, tab0=tab0, n_live=n_live, prune=prune
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(tot), np.asarray(ref_tot))
    return np.asarray(out), np.asarray(tot)


# ---------------------------------------------------------------------------
# Kernel vs oracle — bitwise in fp32 interpret mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prune", [False, True])
def test_kernel_matches_oracle_bitwise(rng, prune):
    args = _random_level(rng)
    out, _ = _check_bitwise(args, n_live=50, prune=prune)
    assert np.abs(out).sum() > 0  # a non-degenerate level


def test_kernel_sharded_addressing(rng):
    """row0/tab0 offsets (spmd: tab0=row0 full-frontier gather; ring:
    tab0=0 own-block gather) match the oracle bitwise."""
    args = _random_level(rng, n=40, t=120, w=16)
    _check_bitwise(args, row0=40, tab0=40, n_live=120, prune=True)
    _check_bitwise(args, row0=80, tab0=0, n_live=120, prune=False)


def test_kernel_traced_row0(rng):
    """row0 may be a traced value (shard_map calls it per-shard)."""
    args = _random_level(rng, n=32, t=96, w=8)

    @jax.jit
    def run(r0):
        return lane_probe_level(*args, row0=r0, tab0=r0, n_live=96,
                                prune=False)

    out, tot = run(jnp.int32(32))
    ref_out, ref_tot = lane_probe_level_ref(
        *args, row0=32, tab0=32, n_live=96, prune=False
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(tot), np.asarray(ref_tot))


# ---------------------------------------------------------------------------
# Edge cases
# ---------------------------------------------------------------------------


def test_all_lanes_dead(rng):
    """Every column finished with no injection: the push is exactly zero
    and the deposit moves every column's scores into the accumulator."""
    n, w = 30, 12
    args = list(_random_level(rng, n=n, w=w))
    args[5] = jnp.ones(w, bool)               # fin: all deposit
    args[6] = jnp.full(w, n, jnp.int32)       # u_p: no injection
    out, tot = _check_bitwise(tuple(args), n_live=n, prune=False)
    assert np.all(out == 0.0)
    np.testing.assert_array_equal(
        tot, np.asarray(args[4]) + np.asarray(args[3])
    )


def test_single_active_column(rng):
    """One live column among finished ones (the tail of a draining batch)."""
    n, w = 30, 9
    args = list(_random_level(rng, n=n, w=w))
    fin = np.ones(w, bool)
    fin[4] = False
    args[5] = jnp.asarray(fin)
    out, _ = _check_bitwise(tuple(args), n_live=n, prune=True)
    assert np.abs(out[:, 4]).sum() > 0
    # finished columns receive only their injections (table lanes zeroed)
    dead = np.delete(np.arange(w), 4)
    inj = np.delete(np.asarray(args[6]), 4) < n
    assert np.all((np.abs(out[:, dead]).sum(axis=0) > 0) == inj)


def test_sentinel_dump_row_contributes_nothing(rng):
    """Neighbor ids >= n_live (the ELL pad sentinel / dump row) are
    value-masked: rows whose slots are ALL sentinels push exactly zero."""
    n = 30
    args = list(_random_level(rng, n=n, w=8))
    nbrs = np.asarray(args[0]).copy()
    nbrs[7, :] = n  # row 7: nothing but sentinels
    args[0] = jnp.asarray(nbrs)
    args[7] = jnp.full(8, n, jnp.int32)  # no exclusion hits
    out, _ = _check_bitwise(tuple(args), n_live=n, prune=False)
    assert np.all(out[7] == 0.0)


@pytest.mark.parametrize("n,w", [(30, 37), (130, 24), (7, 128)])
def test_awkward_shapes(rng, n, w):
    """W not a lane multiple, R above one row tile, tiny R: the wrapper's
    padding must be invisible."""
    args = _random_level(rng, n=n, w=w)
    _check_bitwise(args, n_live=n, prune=True)


def test_bf16_storage_fp32_accumulate(rng):
    """bf16 table/dep/total storage: kernel == oracle bitwise, and the
    deposit accumulates in fp32 (a bf16-storage total still gains deposits
    smaller than its own ulp would allow after many levels)."""
    args = _random_level(rng, dtype=jnp.bfloat16)
    out, tot = _check_bitwise(args, n_live=50, prune=False)
    assert out.dtype == jnp.bfloat16 and tot.dtype == jnp.bfloat16
    f32 = lane_probe_level_ref(
        args[0], args[1], args[2].astype(jnp.float32),
        args[3].astype(jnp.float32), args[4].astype(jnp.float32),
        *args[5:], row0=0, tab0=0, n_live=50, prune=False,
    )[0]
    assert np.abs(out.astype(np.float32) - np.asarray(f32)).max() < 2e-2


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
# End-to-end local serve: use_kernel=True == XLA ELL lane probe, bitwise
# ---------------------------------------------------------------------------


def test_local_serve_kernel_bitwise(small_powerlaw, key):
    from repro.core import make_params
    from repro.core.multisource import multi_source

    d = small_powerlaw
    params = make_params(d["n"], c=0.6, eps_a=0.2, n_r_override=256)
    us = jnp.array([3, 11, 3], jnp.int32)
    xla = multi_source(key, d["eg"], d["eg"], us, params, lanes=96)
    kern = multi_source(key, d["eg"], d["eg"], us, params, lanes=96,
                        use_kernel=True)
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(kern))


def test_local_serve_kernel_bf16(small_powerlaw, key):
    """bf16 score storage through every level stays within 1e-3 of fp32
    on unit-scale SimRank estimates."""
    from repro.core import make_params
    from repro.core.multisource import multi_source

    d = small_powerlaw
    params = make_params(d["n"], c=0.6, eps_a=0.2, n_r_override=256)
    us = jnp.array([3, 11], jnp.int32)
    f32 = multi_source(key, d["eg"], d["eg"], us, params, lanes=96,
                       use_kernel=True)
    bf16 = multi_source(key, d["eg"], d["eg"], us, params, lanes=96,
                        use_kernel=True, kernel_dtype="bfloat16")
    assert np.abs(np.asarray(f32) - np.asarray(bf16)).max() < 1e-3


def test_local_epoch_kernel_bitwise(small_powerlaw, key):
    """The fused local epoch's probe stage under use_kernel=True matches
    the XLA epoch bitwise (same walks, same lane schedule)."""
    from repro.api import GraphHandle, LocalBackend
    from repro.core import make_params
    from repro.graph.dynamic import make_update_batch

    d = small_powerlaw
    p = make_params(d["n"], c=0.6, eps_a=0.2, delta=0.01)
    rng = np.random.default_rng(7)
    ins = (rng.integers(0, d["n"], 8).astype(np.int32),
           rng.integers(0, d["n"], 8).astype(np.int32))
    batch = make_update_batch(ins[0], ins[1], True, batch_size=8, n=d["n"])
    keys = jax.random.split(key, 2)
    outs = []
    for uk in (False, True):
        h = GraphHandle.from_edges(d["src"], d["dst"], d["n"],
                                   capacity=len(d["src"]) + 64)
        be = LocalBackend(h, params=p, walk_chunk=128, use_kernel=uk)
        applied, est, _, _ = be.epoch_batch(
            batch, [3, 11], keys, n_r=128, top_k=0
        )
        assert applied.sum() == 8
        outs.append(est)
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# Sharded mesh paths (subprocess: 8 fake host devices)
# ---------------------------------------------------------------------------

_MESH_KERNEL_SCRIPT = r"""
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

# spmd: fused kernel vs XLA scatter push (same walks, same lane schedule;
# gather- vs scatter-ordered sums => tolerance, not bitwise)
sh_x = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512)
sh_k = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                      use_kernel=True)
a, _, _, _ = sh_x.serve_batch("single_source", nodes, kb, n_r=512)
b, _, _, _ = sh_k.serve_batch("single_source", nodes, kb, n_r=512)
assert np.abs(a - b).max() < 1e-4, np.abs(a - b).max()

# bf16 frontier exchange (kernel + XLA paths) vs fp32 wire
for uk in (True, False):
    bf = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                        use_kernel=uk, frontier_dtype="bfloat16")
    c, _, _, _ = bf.serve_batch("single_source", nodes, kb, n_r=512)
    ref = b if uk else a
    assert np.abs(ref - c).max() < 1e-3, (uk, np.abs(ref - c).max())
print("SPMD_KERNEL_OK")

# ring: the kernel (identity-gather prep fusing deposit+inject+prune)
# keeps the XLA ring push => BITWISE equality
ring_x = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                        probe="ring")
ring_k = ShardedBackend(h.shard(shards=4), params=p, walk_chunk=512,
                        probe="ring", use_kernel=True)
e, _, _, _ = ring_x.serve_batch("single_source", nodes, kb, n_r=512)
f, _, _, _ = ring_k.serve_batch("single_source", nodes, kb, n_r=512)
assert np.array_equal(e, f), np.abs(e - f).max()
print("RING_KERNEL_OK")

# top-k rides the same probe
_, ix, vx, _ = sh_x.serve_batch("topk", nodes, kb, k=5, n_r=512)
_, ik, vk, _ = sh_k.serve_batch("topk", nodes, kb, k=5, n_r=512)
assert all(len(set(ix[i].tolist()) & set(ik[i].tolist())) >= 4
           for i in range(3))

# fused mesh epoch: kernel probe stage vs the chunk-scan epoch
rng = np.random.default_rng(3)
ins = (rng.integers(0, n, 8).astype(np.int32),
       rng.integers(0, n, 8).astype(np.int32))
ekey = jax.random.key(55)
qs = lambda: [QuerySpec(kind="single_source", node=u,
                        key=jax.random.fold_in(ekey, u))
              for u in nodes[:2]]
s1 = SimRankSession(h, seed=0, top_k=5, batch_q=2, update_batch=16,
                    walk_chunk=256, backend="sharded", shards=4)
s2 = SimRankSession(h, seed=0, top_k=5, batch_q=2, update_batch=16,
                    walk_chunk=256, backend="sharded", shards=4,
                    use_kernel=True)
e1 = s1.epoch(inserts=ins, queries=qs(), budget_walks=256)
e2 = s2.epoch(inserts=ins, queries=qs(), budget_walks=256)
assert e1.updates_applied == e2.updates_applied == 8
g1 = np.stack([r.scores for r in e1.results])
g2 = np.stack([r.scores for r in e2.results])
assert np.abs(g1 - g2).max() < 1e-3, np.abs(g1 - g2).max()
print("EPOCH_KERNEL_OK")
"""


def test_sharded_kernel_parity_on_fake_mesh():
    """use_kernel=True on the mesh: spmd fused kernel vs XLA scatter
    (1e-4), bf16 frontier wire (1e-3), ring kernel bitwise, top-k overlap
    and the fused epoch's kernel probe stage — 8 fake XLA host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _MESH_KERNEL_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SPMD_KERNEL_OK" in out.stdout
    assert "RING_KERNEL_OK" in out.stdout
    assert "EPOCH_KERNEL_OK" in out.stdout


# ---------------------------------------------------------------------------
# CSR level (the default path's push on the TPU) vs the XLA COO level
# ---------------------------------------------------------------------------


def _csr_case(rng, case):
    """A COO graph and one level's lane state for a CSR parity case."""
    from repro.graph.structs import graph_from_edges

    n, m, w = 90, 600, 40
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n // 2, m)  # rows n/2 .. n-1 take no in-edges
    cap = m
    if case == "padding":
        cap = m + 77  # padding edges (dst = n) sort past the live rows
    if case == "hub":
        dst[:300] = 5  # a row longer than two id chunks of 128
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
    thr = rng.random(w) * 0.1
    f32, i32 = jnp.float32, jnp.int32
    return g, (jnp.asarray(scores, f32), jnp.asarray(total, f32),
               jnp.asarray(fin), jnp.asarray(u_p, i32),
               jnp.asarray(u_prev, i32), jnp.asarray(thr, f32))


@pytest.mark.parametrize("prune", [False, True])
@pytest.mark.parametrize(
    "case", ["padding", "hub", "empty_rows", "dead_fin", "sentinels"]
)
def test_csr_level_matches_coo_level(rng, case, prune):
    """The CSR kernel (interpret mode) against the XLA COO level at 1e-5:
    padding edges, a hub row over several id chunks, rows with no
    in-edges, dead and depositing columns, sentinel u_p/u_prev."""
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
    assert np.abs(out).sum() > 0  # a non-degenerate level


def _serve_and_epoch(d, key):
    """(serve before, epoch, serve after) estimates on small_powerlaw and
    the serves' push paths: the epoch inserts into spare COO slots and
    deletes live edges."""
    from repro.api import GraphHandle, LocalBackend
    from repro.core import make_params
    from repro.graph.dynamic import make_update_batch

    p = make_params(d["n"], c=0.6, eps_a=0.2, delta=0.01)
    h = GraphHandle.from_edges(d["src"], d["dst"], d["n"],
                               capacity=len(d["src"]) + 64)
    be = LocalBackend(h, params=p, walk_chunk=128)
    keys = jax.random.split(key, 2)
    us = [int(u) for u in np.argsort(-np.bincount(d["dst"]))[:2]]
    before, _, _, _ = be.serve_batch("single_source", us, keys, n_r=128)
    paths = [be.push_path]
    rng = np.random.default_rng(7)
    dels = rng.choice(len(d["src"]), 12, replace=False)
    src = np.concatenate([rng.integers(0, d["n"], 12), d["src"][dels]])
    dst = np.concatenate([rng.integers(0, d["n"], 12), d["dst"][dels]])
    batch = make_update_batch(src, dst, np.arange(24) < 12, batch_size=32,
                              n=d["n"])
    applied, epoch, _, _ = be.epoch_batch(batch, us, keys, n_r=128,
                                          top_k=0)
    assert np.asarray(applied)[12:24].all()  # every delete found its edge
    after, _, _, _ = be.serve_batch("single_source", us, keys, n_r=128)
    paths.append(be.push_path)
    return [before, epoch, after], paths


def test_csr_serve_and_epoch_match_xla(small_powerlaw, key, monkeypatch):
    """The fused serve and epoch steps on the CSR path (steered onto it,
    as on a TPU; interpret mode here) match the XLA path at 1e-5, before
    and after an epoch that inserts and deletes: each dispatch's CSR view
    follows the graph version it serves."""
    from repro.core import multisource

    real = multisource.push_path

    def csr(g, width, *, use_kernel):
        path = real(g, width, use_kernel=use_kernel)
        return "csr_kernel" if path == "coo_xla" else path

    jax.clear_caches()  # compiled steps carry the path they traced
    try:
        xla, xla_paths = _serve_and_epoch(small_powerlaw, key)
        monkeypatch.setattr(multisource, "push_path", csr)
        jax.clear_caches()
        got, got_paths = _serve_and_epoch(small_powerlaw, key)
    finally:
        jax.clear_caches()
    assert xla_paths == ["coo_xla"] * 2  # the CPU's default
    assert got_paths == ["csr_kernel"] * 2
    assert not np.array_equal(xla[0], xla[2])  # the update moved answers
    for a, b in zip(xla, got):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6)
