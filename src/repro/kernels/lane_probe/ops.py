"""Public op: one fused compacted-lane probe level with kernel dispatch.

``lane_probe_level`` executes deposit + inject + prune + ELL push +
exclusion for one level of the compacted lane schedule (DESIGN.md §3/§10)
in a single fused pass.  The wrapper owns the TPU shape discipline so
callers never see it:

* rows pad up to the block size (sentinel neighbor ids, zero weights —
  padded rows compute exact zeros and are sliced off);
* lane columns pad up to the 128-wide lane dimension (sentinel u_p/u_prev,
  ``fin`` false, zero thresholds — padded columns are no-ops);
* ``fin`` booleans widen to int32 for the kernel operand;
* the row block shrinks (down to 8 rows) until the id blocks fit SMEM, and
  a shape whose blocks fit no on-chip budget raises ``ValueError`` — the
  kernel keeps the whole gather table in VMEM, so large graphs must run
  with ``use_kernel=False``;
* ``row0``/``tab0`` (global id of output row 0 / its table row) may be
  python ints or traced values (the sharded paths call this inside
  shard_map with a per-shard ``row0``).

Storage dtype follows ``table`` (float32, or bfloat16 for the bf16-storage
/ fp32-accumulate option); ``dep``/``total`` must match.  Runs the Pallas
kernel natively on TPU and in interpret mode elsewhere, keeping the path
CI-testable on CPU.

``lane_probe_csr_level`` runs the same level over the in-CSR view of a
COO push graph (``csr_push_view``), in fp32 on buffers of
``csr_layout(n)`` rows; ``csr_level_fits`` says whether a graph's
frontier fits it on chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.lane_probe.lane_probe import (
    _EFF_ROWS,
    CSR_CHUNK,
    SMEM_LIMIT_BYTES,
    VMEM_LIMIT_BYTES,
    _pad,
    csr_kernel_bytes,
    kernel_bytes,
    lane_probe_csr_pallas,
    lane_probe_pallas,
)

Array = jax.Array

_LANE = 128  # TPU lane width: pad W up to a multiple of this


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(r: int, block_rows: int) -> tuple[int, int]:
    """(padded_rows, block) — rows pad to a sublane multiple, large row
    counts tile by ``block_rows``."""
    rp = -(-r // 8) * 8
    if rp >= block_rows:
        return -(-rp // block_rows) * block_rows, block_rows
    return rp, rp


def _fit_block(r: int, k: int, t: int, w: int, itemsize: int,
               block_rows: int) -> tuple[int, int]:
    """(padded_rows, block) whose kernel blocks fit SMEM and VMEM; raises
    ``ValueError`` when no block of 8 rows or more does."""
    bn = block_rows
    while True:
        rp, b = _pad_rows(r, bn)
        vmem, smem = kernel_bytes(block_rows=b, k_slots=k, table_rows=t,
                                  width=w, itemsize=itemsize)
        if smem <= SMEM_LIMIT_BYTES and vmem <= VMEM_LIMIT_BYTES:
            return rp, b
        if smem <= SMEM_LIMIT_BYTES or b <= 8:
            raise ValueError(
                f"lane-probe kernel blocks do not fit on chip for "
                f"[{r} rows, K={k}] over a [{t}, {w}] table: {vmem} B of "
                f"VMEM (limit {VMEM_LIMIT_BYTES}) and {smem} B of SMEM "
                f"(limit {SMEM_LIMIT_BYTES}) at {b}-row blocks; run this "
                f"graph with use_kernel=False"
            )
        bn = max(8, b // 16 * 8)


def lane_probe_level(
    nbrs: Array,     # int32 [R, K] global in-neighbor ids (sentinel >= n_live)
    weights: Array,  # f32 [R] push weights (inv_in_deg * sqrt_c)
    table: Array,    # [T, W] gather source (full frontier or own block)
    dep: Array,      # [R, W] pre-level scores of these rows (deposit source)
    total: Array,    # [R, W] per-column accumulator
    fin: Array,      # bool [W] columns depositing this level
    u_p: Array,      # int32 [W] injection ids (>= n_live: no-op)
    u_prev: Array,   # int32 [W] exclusion ids (>= n_live: no-op)
    thr: Array,      # f32 [W] prune thresholds (ignored unless ``prune``)
    *,
    row0,
    tab0,
    n_live: int,
    prune: bool,
    block_rows: int = 128,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Returns ``(scores_out [R, W], total_out [R, W])`` for one level."""
    r, _ = nbrs.shape
    w = table.shape[1]
    if interpret is None:
        interpret = not _on_tpu()

    wp = -(-w // _LANE) * _LANE
    rp, bn = _fit_block(r, nbrs.shape[1], table.shape[0], wp,
                        table.dtype.itemsize, block_rows)
    dtype = table.dtype

    if rp != r:
        pad = rp - r
        nbrs = jnp.concatenate(
            [nbrs, jnp.full((pad, nbrs.shape[1]), n_live, jnp.int32)], axis=0
        )
        weights = jnp.concatenate([weights, jnp.zeros(pad, weights.dtype)])
        dep = jnp.concatenate([dep, jnp.zeros((pad, w), dtype)], axis=0)
        total = jnp.concatenate([total, jnp.zeros((pad, w), dtype)], axis=0)
    if wp != w:
        pad = wp - w
        sent = jnp.full(pad, n_live, jnp.int32)
        fin = jnp.concatenate([fin.astype(jnp.int32), jnp.zeros(pad, jnp.int32)])
        u_p = jnp.concatenate([u_p, sent])
        u_prev = jnp.concatenate([u_prev, sent])
        thr = jnp.concatenate([thr, jnp.zeros(pad, thr.dtype)])
        table = jnp.concatenate(
            [table, jnp.zeros((table.shape[0], pad), dtype)], axis=1
        )
        dep = jnp.concatenate([dep, jnp.zeros((rp, pad), dtype)], axis=1)
        total = jnp.concatenate([total, jnp.zeros((rp, pad), dtype)], axis=1)
    else:
        fin = fin.astype(jnp.int32)

    offs = jnp.stack(
        [jnp.asarray(row0, jnp.int32), jnp.asarray(tab0, jnp.int32)]
    )
    out, tot = lane_probe_pallas(
        nbrs, weights, offs, fin, u_p, u_prev, thr, table, dep, total,
        n_live=n_live, prune=prune, block_rows=bn, interpret=interpret,
    )
    if rp != r or wp != w:
        out = out[:r, :w]
        tot = tot[:r, :w]
    return out, tot


# ---------------------------------------------------------------------------
# CSR level: the default path's push on the TPU
# ---------------------------------------------------------------------------


def csr_layout(n: int, *, block_rows: int = 512) -> tuple[int, int]:
    """(rows, block) of the CSR level's buffers for an n-node graph: the
    live rows, the dump row and at least one zero row, padded to whole
    blocks."""
    bn = min(block_rows, _pad(n + 2, _EFF_ROWS))
    return _pad(n + 2, bn), bn


def csr_level_fits(n: int, w: int) -> bool:
    """Whether the CSR level's fp32 frontier and ids fit on chip for an
    n-node graph at W lane columns."""
    rows, bn = csr_layout(n)
    vmem, smem = csr_kernel_bytes(rows=rows, width=_pad(w, _LANE),
                                  block_rows=bn)
    return vmem <= VMEM_LIMIT_BYTES and smem <= SMEM_LIMIT_BYTES


def csr_push_view(g, weights: Array, *, rows: int, chunk: int = CSR_CHUNK):
    """The CSR level's graph operands from a COO push graph ``g``:
    ``(row_ptr int32 [rows + 1], ids int32 [E], weights f32 [rows, 1])``
    (the weights a column, as the kernel's blocks read them: reshaping
    them a level would copy them a level).

    A stable sort on ``dst`` puts the source ids in dst order; padding
    edges (dst = n) sort last, past ``row_ptr[n]``, and are never read.
    Rows n and up get empty ranges and weight 0; ``ids`` pads to whole
    chunks.  Derive it from the graph version being served (it is one
    sort, so once per dispatch, not per level)."""
    n, cap = g.n, g.dst.shape[0]
    dst, src = jax.lax.sort((g.dst, g.src), num_keys=1, is_stable=True)
    row_ptr = jnp.searchsorted(
        dst, jnp.minimum(jnp.arange(rows + 1, dtype=jnp.int32), n)
    ).astype(jnp.int32)
    ids = jnp.pad(src, (0, _pad(max(cap, 1), chunk) - cap))
    return row_ptr, ids, jnp.pad(weights, (0, rows - n))[:, None]


def lane_probe_csr_level(
    view: tuple[Array, Array, Array],  # csr_push_view(...)
    scores: Array,   # f32 [rows, W] pre-level scores (rows >= n: zero)
    total: Array,    # f32 [rows, W] per-column accumulator
    fin: Array,      # bool [W] columns depositing this level
    u_p: Array,      # int32 [W] injection ids (>= n: no-op)
    u_prev: Array,   # int32 [W] exclusion ids (>= n: no-op)
    thr: Array,      # f32 [W] prune thresholds (ignored unless ``prune``)
    *,
    prune: bool,
    block_rows: int = 512,
    chunk: int = CSR_CHUNK,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """One lane-probe level over the in-CSR view of the push graph:
    ``(scores_out, total_out)`` shaped like ``scores``.  Rows n and up
    (the dump row, the block padding) come back zero in ``scores_out``.
    ``view``'s ``rows`` come from ``csr_layout`` at the same
    ``block_rows``.  W pads to the 128-wide lane dimension here (and is
    cut back)."""
    rows = view[0].shape[0] - 1
    bn = min(block_rows, rows)
    if interpret is None:
        interpret = not _on_tpu()
    w = scores.shape[1]
    wp = _pad(w, _LANE)
    fin = fin.astype(jnp.int32)
    if wp != w:
        lanes = ((0, 0), (0, wp - w))
        scores, total = jnp.pad(scores, lanes), jnp.pad(total, lanes)
        fin, thr = jnp.pad(fin, lanes[1]), jnp.pad(thr, lanes[1])
        u_p = jnp.pad(u_p, lanes[1], constant_values=rows)
        u_prev = jnp.pad(u_prev, lanes[1], constant_values=rows)
    out, tot = lane_probe_csr_pallas(
        *view, fin, u_p, u_prev, thr, scores, total,
        prune=prune, block_rows=bn, chunk=chunk, interpret=interpret,
    )
    if wp != w:
        out, tot = out[:, :w], tot[:, :w]
    return out, tot
