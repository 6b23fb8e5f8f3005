"""ProbeSim single-source & top-k drivers (paper Alg. 1 + Alg. 3 + §4).

Variants (all estimate the same unbiased quantity; tested for agreement):

* ``reference``   — literal Alg. 1/2, python loops (oracle; small inputs).
* ``telescoped``  — the fused serve path (default): the Q = 1 specialization
                    of ``core.multisource.multi_source`` — pooled walk
                    sampling, compacted telescoped probe and the epilogue all
                    in one compiled step (DESIGN.md §3).
* ``tree``        — Alg. 3 prefix-tree batching + telescoping (fastest when
                    n_r is large relative to the distinct-prefix count).
* ``randomized``  — Alg. 4 Bernoulli probes, O(n) per level.

The "best of both worlds" switch (§4.4) is exposed as ``variant='auto'``: it
compares the deterministic cost model (edges touched per level, from degree
stats) against the randomized one (n per level x tree weight) per depth and
picks the cheaper — decided on host from static degree statistics, since TPU
control flow must be shape-static (see DESIGN.md §2).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.multisource import multi_source
from repro.core.params import ProbeSimParams, make_params
from repro.core.probe import (
    estimate_walk_reference,
    probe_tree_levels,
    probe_walks_telescoped,
)
from repro.core.probe_random import randomized_probe_walk
from repro.core.tree import build_prefix_tree
from repro.core.walks import sample_walks
from repro.graph.structs import EllGraph, Graph

Array = jax.Array


def _walk_chunks(n_r: int, chunk: int) -> list[int]:
    sizes = []
    left = n_r
    while left > 0:
        sizes.append(min(chunk, left))
        left -= chunk
    return sizes


def single_source(
    key: Array,
    g: Graph | EllGraph,
    eg: EllGraph,
    u: int,
    params: ProbeSimParams,
    *,
    variant: str = "telescoped",
    walk_chunk: int = 512,
) -> Array:
    """Approximate single-source SimRank: returns estimates [n] (entry u = 1).

    ``g`` is the push representation (COO or ELL), ``eg`` the ELL table used
    for walk sampling (they may be the same object).
    """
    n = eg.n
    sqrt_c = params.sqrt_c
    total = jnp.zeros(n, dtype=jnp.float32)

    if variant == "reference":
        walks = sample_walks(
            key, eg, u, n_r=params.n_r, max_len=params.max_len, sqrt_c=sqrt_c
        )
        for k in range(params.n_r):
            total = total + estimate_walk_reference(
                g, walks[k], sqrt_c, eps_p=params.eps_p
            )
    elif variant == "telescoped":
        # Q = 1 specialization of the fused multi-query serve path: one
        # compiled step samples the whole walk pool, runs the compacted
        # telescoped probe and finalizes the estimate (DESIGN.md §3).
        return multi_source(
            key, g, eg, jnp.asarray([u], jnp.int32), params,
            lanes=walk_chunk,
        )[0]
    elif variant in ("tree", "auto"):
        for ci, b in enumerate(_walk_chunks(params.n_r, walk_chunk)):
            ck = jax.random.fold_in(key, ci)
            # the final partial chunk samples exactly b walks (the seed
            # sampled a full walk_chunk and masked the surplus with a
            # sentinel fill — wasted sampling work)
            walks = sample_walks(
                ck, eg, u, n_r=b, max_len=params.max_len, sqrt_c=sqrt_c
            )
            tree = build_prefix_tree(np.asarray(walks), n)
            if not tree.nodes:  # every walk terminated at u immediately
                continue
            if variant == "auto":
                # best-of-both-worlds (paper §4.4), shape-static form: the
                # tree pays one SpMM per *distinct* prefix column; when the
                # dedup ratio is low the fixed-shape telescoped batch wins
                # (and avoids per-tree recompilation).  Decided per chunk on
                # host from the tree statistics — cf. the paper's dynamic
                # out-degree-sum switch, which is untraceable on TPU.
                from repro.core.tree import tree_stats

                dedup = tree_stats(tree)["dedup_ratio"]
                if dedup < 1.5:
                    total = total + probe_walks_telescoped(
                        g, walks, sqrt_c=sqrt_c, eps_p=params.eps_p,
                    ).sum(axis=1)
                    continue
            total = total + probe_tree_levels(
                g,
                tuple(jnp.asarray(x) for x in tree.nodes),
                tuple(jnp.asarray(x) for x in tree.weights),
                tuple(jnp.asarray(x) for x in tree.parent),
                tuple(jnp.asarray(x) for x in tree.parent_node),
                sqrt_c=sqrt_c,
                eps_p=params.eps_p,
            )
    elif variant == "randomized":
        walks = sample_walks(
            key, eg, u, n_r=params.n_r, max_len=params.max_len, sqrt_c=sqrt_c
        )
        for k in range(params.n_r):
            wk = jax.random.fold_in(key, 10_000 + k)
            total = total + randomized_probe_walk(
                wk, eg, walks[k], sqrt_c=sqrt_c, max_len=params.max_len
            )
    else:
        raise ValueError(f"unknown variant {variant!r}")

    est = total / params.n_r
    if params.truncation_shift:
        est = jnp.where(est > 0, est + params.eps_t / 2, est)
    est = est.at[u].set(1.0)
    return est


def topk(
    key: Array,
    g: Graph | EllGraph,
    eg: EllGraph,
    u: int,
    k: int,
    params: ProbeSimParams,
    **kwargs,
) -> tuple[Array, Array]:
    """Approximate top-k query (paper Def. 2): (nodes [k], estimates [k])."""
    est = single_source(key, g, eg, u, params, **kwargs)
    est = est.at[u].set(-jnp.inf)  # exclude the query node itself
    vals, idx = jax.lax.top_k(est, k)
    return idx, vals


def single_source_simple(
    key: Array,
    eg,
    u: int,
    *,
    n: int | None = None,
    c: float = 0.6,
    eps_a: float = 0.1,
    delta: float = 0.01,
    **kwargs,
) -> Array:
    """DEPRECATED convenience wrapper — prefer a ``GraphHandle``.

    The legacy form takes a bare ``EllGraph`` and silently uses it as BOTH
    the push and the gather representation (i.e. it is exactly
    ``single_source(key, eg, eg, u, ...)`` — correct, but it forfeits the
    COO push mirror without saying so).  Pass a
    :class:`repro.api.GraphHandle` instead and the mirror choice is
    explicit: the handle's COO ``g`` pushes, its ELL ``eg`` gathers.
    """
    from repro.api.handle import GraphHandle  # local: core <-> api layering

    if isinstance(eg, GraphHandle):
        params = make_params(n or eg.n, c=c, eps_a=eps_a, delta=delta)
        return single_source(key, eg.g, eg.eg, u, params, **kwargs)
    import warnings

    warnings.warn(
        "single_source_simple(eg) uses the ELL table as both the push and "
        "gather mirror; pass a repro.api.GraphHandle (explicit mirrors) or "
        "call single_source / SimRankSession.query directly",
        DeprecationWarning,
        stacklevel=2,
    )
    params = make_params(n or eg.n, c=c, eps_a=eps_a, delta=delta)
    return single_source(key, eg, eg, u, params, **kwargs)
