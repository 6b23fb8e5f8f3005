"""Power Method for SimRank (Jeh & Widom) — ground truth on small graphs.

Uses the correct formulation (paper Eq. 10):  S = (c P^T S P) v I  with the
element-wise maximum against I, iterated from S = I.  O(n^2) memory — only
for graphs small enough to verify against (the paper uses 55 iterations for
1e-12 accuracy on its four small datasets).

Also provides the *truncated* power method single-source column, which is
exactly the accuracy envelope of the TopSim family (paper §2.3: TopSim-SM's
estimate equals the Power Method with T iterations, error up to c^T).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.graph.structs import Graph

Array = jax.Array


def _transition_dense(g: Graph) -> Array:
    """P[x, v] = 1/|I(v)| if (x -> v) else 0 (column-stochastic over in-edges)."""
    n = g.n
    mask = g.edge_mask()
    src = jnp.where(mask, g.src, 0)
    dst = jnp.where(mask, g.dst, 0)
    A = jnp.zeros((n, n), jnp.float32).at[src, dst].add(
        mask.astype(jnp.float32)
    )
    return A * g.inv_in_deg[None, :]


@partial(jax.jit, static_argnames=("iters", "c"))
def simrank_power(g: Graph, *, c: float = 0.6, iters: int = 55) -> Array:
    """All-pairs SimRank S [n, n] by the Power Method."""
    P = _transition_dense(g)
    n = g.n
    eye = jnp.eye(n, dtype=jnp.float32)

    # fp32 matmuls: the default precision runs bf16 passes on a TPU, which
    # would leave the reference good to only ~1e-3
    hi = jax.lax.Precision.HIGHEST

    def body(_, S):
        S = c * jnp.matmul(jnp.matmul(P.T, S, precision=hi), P, precision=hi)
        return jnp.maximum(S, eye)

    return jax.lax.fori_loop(0, iters, body, eye)


def simrank_power_host(
    src: np.ndarray, dst: np.ndarray, n: int, *, c: float = 0.6, iters: int = 55
) -> np.ndarray:
    """Numpy variant for host-side test fixtures."""
    A = np.zeros((n, n), dtype=np.float64)
    np.add.at(A, (src, dst), 1.0)
    in_deg = A.sum(axis=0)
    P = A / np.maximum(in_deg[None, :], 1.0)
    S = np.eye(n)
    for _ in range(iters):
        S = np.maximum(c * (P.T @ S @ P), np.eye(n))
    return S


@partial(jax.jit, static_argnames=("iters", "c"))
def simrank_truncated_single_source(
    g: Graph, u: Array, *, c: float = 0.6, iters: int = 3
) -> Array:
    """s_T(u, .) — Power Method truncated at T iterations (TopSim accuracy).

    This is the estimate quality of TopSim-SM with walk depth T (paper §2.3);
    the absolute error can reach c^T (= 0.216 at T=3, c=0.6), which is the
    effect the paper's Figure 4 demonstrates.
    """
    S = simrank_power(g, c=c, iters=iters)
    return S[u]
