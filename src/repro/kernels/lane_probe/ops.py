"""Public op: one fused compacted-lane probe level over a CSR push view.

``lane_probe_csr_level`` executes deposit + inject + prune + push +
exclusion for one level of the compacted lane schedule (DESIGN.md §3/§10)
in a single fused pass, over the in-CSR view of a COO push graph
(``csr_push_view``), in fp32 on buffers of ``csr_layout(n)`` rows;
``csr_level_fits`` says whether a graph's frontier fits it on chip.  The
wrapper owns the TPU shape discipline so callers never see it: lane
columns pad up to the 128-wide lane dimension (sentinel u_p/u_prev,
``fin`` false, zero thresholds: padded columns are no-ops) and ``fin``
widens to int32 for the kernel operand.  It runs the Pallas kernel
natively on a TPU and in interpret mode elsewhere, keeping the path
testable on the CPU.
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
    lane_probe_csr_pallas,
)

Array = jax.Array

_LANE = 128  # TPU lane width: pad W up to a multiple of this


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


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
