"""Distributed ProbeSim correctness on a local 8-device mesh.

Needs XLA_FLAGS set before jax init, so the meshed half runs in a
subprocess; it must produce results identical to the unsharded run (JAX PRNG
values are sharding-invariant)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.distributed import (
    build_sharded_graph,
    make_serve_step,
    probe_walks_sharded,
    sample_walks_sharded,
)
from repro.core.probe import probe_walks_telescoped
from repro.graph import graph_from_edges, powerlaw_graph

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import ProbeSimConfig
from repro.core.distributed import build_sharded_graph, make_serve_step, graph_specs
from repro.graph import powerlaw_graph
from repro.utils.jaxcompat import make_mesh, specs_to_shardings
from jax.sharding import PartitionSpec as P

mesh = make_mesh((2, 4), ("data", "model"))
src, dst, n = powerlaw_graph(200, 1600, seed=3)
sg = build_sharded_graph(src, dst, n, pad_nodes=32, pad_edges=64)
cfg = ProbeSimConfig(name="t", n=n, m=len(src), c=0.6)
serve = make_serve_step(cfg, queries=2, walk_chunk=32, max_len=6, top_k=8,
                        edge_chunks=4)
queries = jnp.asarray([int(dst[0]), int(dst[1])], jnp.int32)
key = jax.random.key(7)
with jax.set_mesh(mesh):
    jf = jax.jit(serve, in_shardings=specs_to_shardings(
        (graph_specs(sg), P(), P()), mesh=mesh))
    idx, vals = jf(sg, queries, key)
print(json.dumps(dict(idx=np.asarray(idx).tolist(),
                      vals=np.asarray(vals).tolist())))
"""


@pytest.fixture(scope="module")
def small_graph():
    src, dst, n = powerlaw_graph(200, 1600, seed=3)
    return src, dst, n


def _unsharded_serve(src, dst, n):
    from repro.configs.base import ProbeSimConfig

    sg = build_sharded_graph(src, dst, n, pad_nodes=32, pad_edges=64)
    cfg = ProbeSimConfig(name="t", n=n, m=len(src), c=0.6)
    serve = make_serve_step(cfg, queries=2, walk_chunk=32, max_len=6, top_k=8,
                            edge_chunks=4)
    queries = jnp.asarray([int(dst[0]), int(dst[1])], jnp.int32)
    return jax.jit(serve)(sg, queries, jax.random.key(7))


def test_sharded_probe_equals_telescoped(small_graph, key):
    src, dst, n = small_graph
    sg = build_sharded_graph(src, dst, n, pad_nodes=32, pad_edges=64)
    g = graph_from_edges(src, dst, n)
    walks = sample_walks_sharded(
        key, sg, jnp.asarray([int(dst[0])], jnp.int32),
        walks_per_query=16, max_len=6, sqrt_c=0.775,
    )
    # clip sentinel coding: sharded uses n_pad; local uses n
    walks_local = jnp.where(walks >= n, n, walks)
    a = probe_walks_sharded(sg, walks, sqrt_c=0.775, edge_chunks=4)[:n]
    b = probe_walks_telescoped(g, walks_local, sqrt_c=0.775)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_meshed_serve_step_matches_unsharded(small_graph):
    src, dst, n = small_graph
    idx0, vals0 = _unsharded_serve(src, dst, n)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(
        np.asarray(data["vals"]), np.asarray(vals0), atol=1e-5
    )
    # node sets should agree (order may tie-break differently)
    for q in range(2):
        assert set(data["idx"][q]) == set(np.asarray(idx0[q]).tolist())


_RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core.ring import build_ring_graph, probe_walks_ring
from repro.core.distributed import build_sharded_graph, probe_walks_sharded, sample_walks_sharded
from repro.utils.jaxcompat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.graph import powerlaw_graph
src, dst, n = powerlaw_graph(200, 1600, seed=3)
rg = build_ring_graph(src, dst, n, shards=4)
sg = build_sharded_graph(src, dst, n, pad_nodes=4, pad_edges=64)
key = jax.random.key(5)
with jax.set_mesh(mesh):
    walks = sample_walks_sharded(key, sg, jnp.asarray([int(dst[0])], jnp.int32),
                                 walks_per_query=16, max_len=6, sqrt_c=0.775)
    ref = probe_walks_sharded(sg, walks, sqrt_c=0.775, edge_chunks=4)
    walks_r = jnp.where(walks >= sg.n_pad, rg.n_pad, walks)
    out = probe_walks_ring(rg, walks_r, sqrt_c=0.775)
    np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(ref[:n]), atol=1e-5)
    out16 = probe_walks_ring(rg, walks_r, sqrt_c=0.775, frontier_dtype=jnp.bfloat16)
    err = np.abs(np.asarray(out16[:n], np.float32) - np.asarray(ref[:n])).max()
    assert err < 2e-3, err
    print("RING_OK", err)
"""


def test_ring_push_matches_auto_partitioned():
    """The SS4.4/Perf ring variant (shard_map + ppermute, bf16 bitcast) is
    numerically identical (fp32) / eps-close (bf16) to the baseline."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _RING_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RING_OK" in out.stdout
