"""Pallas TPU kernel: one fused compacted-lane probe level over a CSR graph.

The compacted telescoped probe (DESIGN.md §3/§10) runs, per level and per
lane column c:

    deposit   total[:, c] += scores[:, c]            if fin[c]
    inject    scores[u_p[c], c] += 1                  (sentinel = no-op)
    prune     scores[:, c] = 0 where <= thr[c]
    push      out[v, c] = w[v] * sum_{x in I(v)} scores[x, c]
    exclude   out[u_prev[c], c] = 0

The XLA lowering issues these as five separate scatter/gather/select HLOs,
each streaming the whole [rows, W] buffer through HBM.  This kernel runs
the level in ONE pass: inject and prune are folded into what each source
row sends (``_eff``, computed once per row on the VMEM copy of the
frontier), the push gathers one frontier row per in-edge, and the deposit
and exclusion are fused into each output block's store.  Its operands are
a row pointer over the output rows and the source ids in dst order, a
view the caller derives from the COO push graph once per dispatch
(``ops.csr_push_view``), so a level costs one gather per edge.

TPU mapping:

* The whole fp32 frontier is DMA'd into one VMEM scratch at the first
  grid step and turned, in place, into what each source row sends this
  level; every later step gathers rows of it.  The frontier therefore has
  to fit VMEM (``csr_kernel_bytes``; ``ops.csr_level_fits``).
* The row pointer is a scalar-prefetch operand, so each row's edge range
  is two SMEM reads.
* The source ids stay in HBM and stream through two SMEM slots of
  ``chunk`` ids each, in edge order; edge e's id sits at e mod 2*chunk.
  Moving to a chunk starts the load of the next.  A row whose edges lie
  in the chunk being read (nearly every row) sums them in one loop; a
  row that crosses into the next chunk walks its range chunk by chunk,
  so a hub row of any length reads the same way.  Rows are visited in
  order and their ranges tile [0, m), so the chunks are visited in order
  too; the grid therefore runs sequentially ("arbitrary").
* Edges are gathered ``_UNROLL`` at a time; the last group of a row
  pads with the buffer's last row, a block-padding row that sends exact
  zeros, so the buffers hold at least n + 2 rows.
* Rows past the graph (the dump row and the block padding) carry weight
  0 and an empty range, so they are written as exact zeros.
* Output rows tile in blocks of BN; the lane-column dim W rides the
  128-wide lane dimension (the op wrapper pads W up); the per-column lane
  state (fin/u_p/u_prev/thr) is tiny [1, W] blocks replicated to every
  grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Scoped VMEM the kernel may ask Mosaic for (TPU v5e has 128 MiB of VMEM
# per core; the rest is left to the surrounding XLA program).
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
# SMEM of one TPU v5e core (1 MiB), less room for Mosaic's own scalars.
SMEM_LIMIT_BYTES = 1024 * 1024 - 64 * 1024


def _pad(x: int, m: int) -> int:
    return -(-x // m) * m


def _eff(rows, ids, fin, u_p, thr, *, prune: bool):
    """What a source row sends in one level: a depositing column reads
    zero, the column's walk injects its unit mass at ``u_p``, and pruning
    drops mass at or under the column's threshold.  ``rows`` is fp32
    [r, W], ``ids`` the rows' node ids [r, W], the lane state [1, W]."""
    eff = jnp.where(fin, 0.0, rows) + (u_p == ids).astype(jnp.float32)
    if prune:
        eff = jnp.where(eff > thr, eff, 0.0)
    return eff


# Ids per SMEM slot (two slots: 64 KiB).
CSR_CHUNK = 8192
# Gathers issued together per row (timed best of 2, 4 and 8 on a v5e).
_UNROLL = 4
# Rows per step of the in-place ``_eff`` pass over the frontier.
_EFF_ROWS = 64


def csr_kernel_bytes(*, rows: int, width: int, block_rows: int,
                     chunk: int = CSR_CHUNK) -> tuple[int, int]:
    """(VMEM, SMEM) bytes of the CSR level kernel over an fp32 [rows,
    width] frontier: the frontier scratch, the pipelined tiles and lane
    state, and in SMEM the row pointer and the two id slots."""
    frontier = _pad(rows, 8) * width * 4
    tiles = 4 * 2 * block_rows * width * 4  # dep/total in, out/tot out
    weights = 2 * block_rows * 128 * 4  # [bn, 1] blocks, lane-padded
    lanes = 4 * 2 * 8 * width * 4
    acc = block_rows * width * 4
    smem = (_pad(rows + 1, 128) + 2 * chunk + 128) * 4
    return frontier + tiles + weights + lanes + acc, smem


def _csr_kernel(
    rp_ref,      # SMEM int32 [R + 1] row pointer, scalar prefetch
    ids_hbm,     # HBM int32 [E]     source ids in dst order, chunk-padded
    w_ref,       # f32   [bn, 1]     push weights of this block
    fin_ref,     # int32 [1, W]      1 where the column deposits this level
    up_ref,      # int32 [1, W]      injection node id (sentinel: no-op)
    uprev_ref,   # int32 [1, W]      exclusion node id (sentinel: no-op)
    thr_ref,     # f32   [1, W]      per-column prune threshold
    table_hbm,   # HBM f32 [R, W]    pre-level scores, whole
    dep_ref,     # f32 [bn, W]       the same scores, this block
    total_ref,   # f32 [bn, W]       accumulator block
    out_ref,     # f32 [bn, W]       pushed scores out
    tot_ref,     # f32 [bn, W]       updated accumulator out
    eff_ref,     # VMEM f32 [R, W]   what each source row sends
    ids_ref,     # SMEM int32 [2 * chunk] two id slots
    cur_ref,     # SMEM int32 [1]    the chunk being read
    sem,         # DMA semaphores [3]: frontier, id slot 0, id slot 1
    acc_ref,     # VMEM f32 [bn, W]  summed rows of this block
    *,
    bn: int,
    rows: int,
    chunk: int,
    prune: bool,
):
    pid = pl.program_id(0)
    width = acc_ref.shape[1]
    fin = fin_ref[...] != 0
    log_chunk = chunk.bit_length() - 1
    n_chunks = jax.lax.shift_right_logical(rp_ref[rows] + chunk - 1,
                                           log_chunk)

    def id_copy(c):
        slot = c & 1
        return pltpu.make_async_copy(
            ids_hbm.at[pl.ds(c * chunk, chunk)],
            ids_ref.at[pl.ds(slot * chunk, chunk)],
            sem.at[1 + slot],
        )

    @pl.when(pid == 0)
    def _prologue():
        frontier = pltpu.make_async_copy(table_hbm, eff_ref, sem.at[0])
        frontier.start()

        @pl.when(n_chunks > 0)
        def _():
            id_copy(0).start()

        if ids_hbm.shape[0] > chunk:
            @pl.when(n_chunks > 1)
            def _():
                id_copy(1).start()

        frontier.wait()
        u_p = up_ref[...]
        thr = thr_ref[...]

        def eff_block(b, carry):
            r = pl.multiple_of(b * _EFF_ROWS, _EFF_ROWS)
            ids = r + jax.lax.broadcasted_iota(
                jnp.int32, (_EFF_ROWS, width), 0
            )
            eff_ref[pl.ds(r, _EFF_ROWS), :] = _eff(
                eff_ref[pl.ds(r, _EFF_ROWS), :], ids, fin, u_p, thr,
                prune=prune,
            )
            return carry

        jax.lax.fori_loop(0, rows // _EFF_ROWS, eff_block, 0)

        @pl.when(n_chunks > 0)
        def _():
            id_copy(0).wait()

        cur_ref[0] = 0

    # deposit
    tot_ref[...] = total_ref[...] + jnp.where(fin, dep_ref[...], 0.0)

    log_u = _UNROLL.bit_length() - 1
    slots = 2 * chunk - 1  # edge index -> its id's place in the two slots
    zero_row = rows - 1  # a block-padding row: sends exact zeros

    def span_sum(e0, e1, acc):
        """``acc`` plus the rows of edges [e0, e1), whose ids are loaded:
        groups of ``_UNROLL`` gathers, the last padded with the zero row."""

        def group(t, acc):
            parts = []
            for u in range(_UNROLL):
                e = e0 + t * _UNROLL + u
                idx = jnp.where(e < e1, ids_ref[e & slots], zero_row)
                parts.append(eff_ref[pl.ds(idx, 1), :])
            while len(parts) > 1:
                parts = [a + b for a, b in zip(parts[::2], parts[1::2])]
            return acc + parts[0]

        groups = jax.lax.shift_right_logical(e1 - e0 + _UNROLL - 1, log_u)
        return jax.lax.fori_loop(0, groups, group, acc)

    def segment(state, end):
        """Sum a row's edges up to the end of the next chunk they reach,
        moving the slots to that chunk first."""
        e0, acc = state
        c = jax.lax.shift_right_logical(e0, log_chunk)

        @pl.when(c != cur_ref[0])
        def _advance():
            id_copy(c).wait()

            @pl.when(c + 1 < n_chunks)
            def _():
                id_copy(c + 1).start()

            cur_ref[0] = c

        e1 = jnp.minimum(end, (c + 1) * chunk)
        return e1, span_sum(e0, e1, acc)

    def row_body(i, carry):
        start = rp_ref[pid * bn + i]
        end = rp_ref[pid * bn + i + 1]
        zero = jnp.zeros((1, width), jnp.float32)

        def crossing():
            return jax.lax.while_loop(
                lambda s: s[0] < end, lambda s: segment(s, end), (start, zero)
            )[1]

        # the common row: empty, or every edge in the chunk being read
        last = jax.lax.shift_right_logical(end - 1, log_chunk)
        acc_ref[pl.ds(i, 1), :] = jax.lax.cond(
            (last == cur_ref[0]) | (end == start),
            lambda: span_sum(start, end, zero), crossing,
        )
        return carry

    jax.lax.fori_loop(0, bn, row_body, 0)
    gid = pid * bn + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    out_ref[...] = jnp.where(
        uprev_ref[...] == gid, 0.0, acc_ref[...] * w_ref[...]
    )


@functools.partial(
    jax.jit,
    static_argnames=("prune", "block_rows", "chunk", "interpret"),
)
def lane_probe_csr_pallas(
    row_ptr: Array,  # int32 [R + 1]
    ids: Array,      # int32 [E], E a multiple of ``chunk``
    weights: Array,  # f32 [R, 1]
    fin: Array,      # int32 [W]
    u_p: Array,      # int32 [W]
    u_prev: Array,   # int32 [W]
    thr: Array,      # f32 [W]
    scores: Array,   # f32 [R, W]
    total: Array,    # f32 [R, W]
    *,
    prune: bool,
    block_rows: int = 512,
    chunk: int = CSR_CHUNK,
    interpret: bool = True,
) -> tuple[Array, Array]:
    R, W = scores.shape
    bn = block_rows
    assert R % bn == 0 and bn % _EFF_ROWS == 0, (R, bn)
    assert ids.shape[0] % chunk == 0, (ids.shape, chunk)
    assert chunk & (chunk - 1) == 0  # shifts and masks divide by it
    kernel = functools.partial(
        _csr_kernel, bn=bn, rows=R, chunk=chunk, prune=prune,
    )
    lane = pl.BlockSpec((1, W), lambda i, rp: (0, 0))
    tile = pl.BlockSpec((bn, W), lambda i, rp: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // bn,),
        in_specs=[
            hbm,
            pl.BlockSpec((bn, 1), lambda i, rp: (i, 0)),
            lane, lane, lane, lane,
            hbm,
            tile, tile,
        ],
        out_specs=[tile, tile],
        scratch_shapes=[
            pltpu.VMEM((R, W), jnp.float32),
            pltpu.SMEM((2 * chunk,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.VMEM((bn, W), jnp.float32),
        ],
    )
    need, _ = csr_kernel_bytes(rows=R, width=W, block_rows=bn, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, W), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(need + (4 << 20), VMEM_LIMIT_BYTES),
        ),
        input_output_aliases={9: 1},  # total -> tot, block by block
        interpret=interpret,
    )(
        row_ptr, ids, weights,
        fin.reshape(1, W), u_p.reshape(1, W), u_prev.reshape(1, W),
        thr.reshape(1, W), scores, scores, total,
    )
