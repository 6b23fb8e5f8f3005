"""Plain reference: exact SimRank by the Power Method, on the device.

    S_0 = I,   S_{k+1} = c * W^T S_k W  with the diagonal reset to 1,

where ``W[i, a] = 1 / |I(a)|`` for each edge ``i -> a`` (a repeated edge
counts once per copy).  After ``k`` iterations ``0 <= S* - S_k <= c^(k+1)``
entrywise (Lizorkin et al., VLDB 2008), so the iteration count fixes the
reference's own error.

With the edge-count matrix ``A`` and ``D = diag(1 / |I(a)|)``, ``W = A D``
and ``W^T S W = D (A^T (S A)) D``: two dense matrix products per
iteration on the chip's matrix unit, at float32 (``HIGHEST``) precision.
``A`` is kept in bfloat16, exact for edge counts up to 256; each product
takes ``A`` a block of columns at a time, cast to float32, so no float32
copy of ``A`` is made.  Memory: two ``n x n`` float32 matrices, ``A`` in
bfloat16 and one block.  Imports nothing of the program under test.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

BLOCK_BYTES = 256 << 20  # float32 bytes of one block of A's columns


def iterations_for(c: float, tol: float) -> int:
    """Smallest k with c^(k+1) <= tol."""
    return max(1, math.ceil(math.log(tol) / math.log(c)) - 1)


HI = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _right(s, a, r):
    """``S @ A``, ``r`` columns of ``A`` at a time (``S`` is donated)."""
    out = jnp.zeros_like(s)

    def body(j, out):
        blk = jax.lax.dynamic_slice_in_dim(a, j * r, r, 1).astype(jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.dot(s, blk, precision=HI), j * r, 1)

    return jax.lax.fori_loop(0, a.shape[1] // r, body, out)


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _left(y, a, r, scale, c):
    """``c * D (A^T Y) D`` with the diagonal set to 1, ``r`` rows at a time
    (``Y`` is donated)."""
    out = jnp.zeros_like(y)

    def body(j, out):
        blk = jax.lax.dynamic_slice_in_dim(a, j * r, r, 1).astype(jnp.float32)
        rows = jnp.dot(blk.T, y, precision=HI)
        rows = c * jax.lax.dynamic_slice_in_dim(scale, j * r, r)[:, None] \
            * rows * scale[None, :]
        ids = j * r + jnp.arange(r)
        rows = rows.at[jnp.arange(r), ids].set(1.0)
        return jax.lax.dynamic_update_slice_in_dim(out, rows, j * r, 0)

    return jax.lax.fori_loop(0, a.shape[1] // r, body, out)


@partial(jax.jit, static_argnums=(2,))
def _start(src, dst, m):
    """``A`` (bfloat16 edge counts) and ``S_0 = I``, each built in place on
    the device: op by op, the identity alone would pass through two int32
    ``[m, m]`` index arrays."""
    a = jnp.zeros((m, m), jnp.bfloat16).at[src, dst].add(1.0)
    return a, jnp.eye(m, dtype=jnp.float32)


def _padded(src, dst, n: int, c: float, iterations: int, block_bytes: int):
    """SimRank over ``n`` padded up to whole blocks ``[m, m]`` (padded nodes
    have no edges)."""
    blocks = max(1, -(-n * n * 4 // block_bytes))
    r = -(-n // blocks)
    m = r * (-(-n // r))
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(src) and np.unique(src * n + dst, return_counts=True)[1].max() > 256:
        raise ValueError("an edge repeated over 256 times is not exact in bfloat16")
    deg = np.bincount(dst, minlength=m)
    scale = jnp.asarray(np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0),
                        jnp.float32)
    a, x = _start(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), m)
    for _ in range(iterations):
        x = _left(_right(x, a, r), a, r, scale, jnp.float32(c))
    a.delete()
    return x.block_until_ready()


def simrank(src, dst, n: int, *, c: float, iterations: int,
            block_bytes: int = BLOCK_BYTES) -> jax.Array:
    """All-pairs SimRank ``[n, n]`` float32, left on the device."""
    return _padded(src, dst, n, c, iterations, block_bytes)[:n, :n]


def rows(src, dst, n: int, sources, *, c: float, iterations: int) -> dict:
    """``{u: S[u, :]}`` on the host for each requested source."""
    x = _padded(src, dst, n, c, iterations, BLOCK_BYTES)
    us = sorted({int(u) for u in sources})
    got = np.asarray(x[jnp.asarray(us, jnp.int32), :n]) if us else np.zeros((0, n))
    x.delete()
    return dict(zip(us, got))
