"""Distributed ProbeSim serving demo on a local 8-device mesh.

Runs the SAME serve step that the 512-chip dry-run compiles — auto-partitioned
baseline and the ring/bf16 §Perf variant — on 8 fake CPU devices, verifying
they return identical top-k and timing both.

Run:  PYTHONPATH=src python examples/distributed_serve_demo.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ProbeSimConfig
from repro.core.distributed import build_sharded_graph, graph_specs, make_serve_step
from repro.core.ring import build_ring_graph, make_ring_serve_step, ring_graph_specs
from repro.graph import powerlaw_graph


def main():
    from repro.utils.jaxcompat import make_mesh, specs_to_shardings

    mesh = make_mesh((2, 4), ("data", "model"))
    src, dst, n = powerlaw_graph(20_000, 200_000, seed=0)
    cfg = ProbeSimConfig(name="demo", n=n, m=len(src), c=0.6)
    Q, B, L, K = 4, 64, 8, 10
    queries = jnp.asarray(np.unique(dst)[:Q].astype(np.int32))
    key = jax.random.key(0)

    sg = build_sharded_graph(src, dst, n, pad_nodes=32, pad_edges=256)
    rg = build_ring_graph(src, dst, n, shards=4)

    with jax.set_mesh(mesh):
        auto = jax.jit(
            make_serve_step(cfg, queries=Q, walk_chunk=B, max_len=L, top_k=K,
                            edge_chunks=4),
            in_shardings=specs_to_shardings(
                (graph_specs(sg), P(), P()), mesh=mesh),
        )
        ring = jax.jit(
            make_ring_serve_step(cfg, queries=Q, walk_chunk=B, max_len=L,
                                 top_k=K, frontier_dtype=jnp.bfloat16),
            in_shardings=specs_to_shardings(
                (ring_graph_specs(rg), P(), P()), mesh=mesh),
        )

        for name, fn, g in [("auto-partitioned", auto, sg),
                            ("ring+bf16      ", ring, rg)]:
            idx, vals = jax.block_until_ready(fn(g, queries, key))  # compile
            t0 = time.time()
            for _ in range(3):
                idx, vals = jax.block_until_ready(fn(g, queries, key))
            dt = (time.time() - t0) / 3
            print(f"{name}: {dt*1e3:7.1f} ms/step  "
                  f"q0 top3={np.asarray(idx[0][:3]).tolist()} "
                  f"scores={np.round(np.asarray(vals[0][:3], np.float32), 4).tolist()}")

        a_idx, _ = auto(sg, queries, key)
        r_idx, _ = ring(rg, queries, key)
        same = all(
            set(np.asarray(a_idx[q]).tolist()) == set(np.asarray(r_idx[q]).tolist())
            for q in range(Q)
        )
        print(f"top-{K} sets identical across implementations: {same}")


if __name__ == "__main__":
    main()
