"""Vectorized sqrt(c)-walk generation (paper Def. 3).

A sqrt(c)-walk from u follows a uniformly random **in**-neighbor at each step
and terminates with probability 1 - sqrt(c) per step (or at a node with no
in-neighbors).  We generate a batch of walks as a dense int32 matrix
``walks[n_r, max_len]`` with ``walks[:, 0] = u`` and sentinel ``n`` after
termination.  Walks are truncated at ``max_len`` = l_t (Pruning rule 1).

Sampling uses the ELL in-neighbor table: next = in_nbrs[v, floor(r * deg(v))].

Two entry points:

* ``sample_walks``       — n_r walks from a single source (one PRNG stream).
* ``sample_walks_batch`` — Q independent per-query streams, one vmapped
  dispatch.  This is the fused-serving path (DESIGN.md §3): the whole walk
  pool for a query batch is drawn in ONE call, because per-chunk sampling
  pays a large fixed cost per dispatch (the ELL table walk) that a pooled
  call amortizes to noise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.graph.structs import EllGraph

Array = jax.Array


def walk_uniforms(
    key: Array,
    *,
    n_r: int,
    max_len: int,
    sqrt_c: float,
) -> tuple[Array, Array]:
    """Draw the per-(walk, step) randomness for ``n_r`` walks up front.

    Returns ``(cont, pick)``, both [n_r, max_len - 1]: the continue/stop
    coins (bool, continue w.p. sqrt(c)) and the neighbor-pick uniforms.
    Walks are row-independent given these draws, so any row subset can be
    materialized separately (``walks_from_uniforms``) and still be
    bit-identical to a full-pool ``sample_walks`` call.
    """
    k_cont, k_step = jax.random.split(key)
    cont = jax.random.uniform(k_cont, (n_r, max_len - 1)) < sqrt_c
    pick = jax.random.uniform(k_step, (n_r, max_len - 1))
    return cont, pick


def walks_from_uniforms(
    eg: EllGraph,
    u: Array,
    cont: Array,
    pick: Array,
) -> Array:
    """Materialize walks [R, max_len] from pre-drawn uniforms (any row
    subset of a ``walk_uniforms`` batch)."""
    n = eg.n
    n_r = cont.shape[0]

    def step(carry, inputs):
        cur, alive = carry  # cur: [n_r] current node; alive: [n_r] bool
        cont_t, pick_t = inputs
        deg = eg.in_deg[cur.clip(0, n - 1)]
        can_move = alive & cont_t & (deg > 0)
        k = jnp.floor(pick_t * deg.astype(jnp.float32)).astype(jnp.int32)
        k = k.clip(0, jnp.maximum(deg - 1, 0))
        nxt = eg.in_nbrs[cur.clip(0, n - 1), k]
        nxt = jnp.where(can_move, nxt, n)
        return (nxt, can_move), nxt

    u_col = jnp.broadcast_to(jnp.asarray(u, jnp.int32), (n_r,))
    (_, _), cols = jax.lax.scan(
        step, (u_col, jnp.ones(n_r, dtype=bool)), (cont.T, pick.T)
    )
    walks = jnp.concatenate([u_col[:, None], cols.T], axis=1)
    return walks.astype(jnp.int32)


def _sample_walks_impl(
    key: Array,
    eg: EllGraph,
    u: Array,
    *,
    n_r: int,
    max_len: int,
    sqrt_c: float,
) -> Array:
    """Trace-level body shared by the single- and multi-query entry points."""
    cont, pick = walk_uniforms(key, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c)
    return walks_from_uniforms(eg, u, cont, pick)


@partial(jax.jit, static_argnames=("n_r", "max_len", "sqrt_c"))
def sample_walks(
    key: Array,
    eg: EllGraph,
    u: Array,
    *,
    n_r: int,
    max_len: int,
    sqrt_c: float,
) -> Array:
    """Sample ``n_r`` sqrt(c)-walks from node ``u``.

    Returns int32 [n_r, max_len]; walks[:, 0] == u; sentinel = n.
    """
    return _sample_walks_impl(
        key, eg, u, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
    )


@partial(jax.jit, static_argnames=("n_r", "max_len", "sqrt_c"))
def sample_walks_batch(
    keys: Array,
    eg: EllGraph,
    us: Array,
    *,
    n_r: int,
    max_len: int,
    sqrt_c: float,
) -> Array:
    """Sample ``n_r`` walks from each of Q sources, one per-query PRNG stream.

    ``keys`` is a [Q] typed key array; ``us`` is int32 [Q].  Returns int32
    [Q, n_r, max_len].  Query q's walks depend only on (keys[q], us[q]), so a
    batched serve produces bit-identical walks to Q separate single-query
    calls with the same per-query keys (exercised by the engine tests).
    """
    us = jnp.asarray(us, jnp.int32)
    return jax.vmap(
        lambda k, u: _sample_walks_impl(
            k, eg, u, n_r=n_r, max_len=max_len, sqrt_c=sqrt_c
        )
    )(keys, us)


def walk_lengths(walks: Array, n: int) -> Array:
    """Number of live nodes per walk (l in the paper)."""
    return (walks < n).sum(axis=1).astype(jnp.int32)
