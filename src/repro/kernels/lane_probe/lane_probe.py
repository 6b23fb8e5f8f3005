"""Pallas TPU kernel: one fused compacted-lane probe level on-chip.

The compacted telescoped probe (DESIGN.md §3/§10) runs, per level and per
lane column c:

    deposit   total[:, c] += scores[:, c]            if fin[c]
    inject    scores[u_p[c], c] += 1                  (sentinel = no-op)
    prune     scores[:, c] = 0 where <= thr[c]
    push      out[v, c] = w[v] * sum_k scores[nbrs[v, k], c]
    exclude   out[u_prev[c], c] = 0

The XLA lowering issues these as five separate scatter/gather/select HLOs,
each streaming the whole [rows, W] block through HBM.  This kernel fuses
the level into ONE pass over the output block: the deposit is a block read
of the pre-level scores, and inject/prune/exclude become per-gathered-element
arithmetic folded into the SpMM gather — the injected unit mass is
reconstructed at gather time from ``u_p`` (the gather address equals the
injection address), so no scatter ever materializes.

TPU mapping:
* output rows tile in blocks of BN; the lane-column dim W rides the 128-wide
  lane dimension (the op wrapper pads W up);
* ``(row0, tab0)`` is a scalar-prefetch operand, and each grid step's
  neighbor ids [BN, K] and push weights [BN, 1] are SMEM blocks, so every
  gather address is a scalar read (prefetching the whole [R, K] id table
  instead would cap R * K at SMEM's 1 MiB);
* the frontier ``table`` is ONE whole [T, W] VMEM block (single-buffered:
  its block never changes across the grid) and each gathered row is a
  dynamic one-row slice of it (packed dtypes read the aligned sublane
  window holding the row).  The table therefore has to fit VMEM and the
  ids SMEM: :func:`kernel_bytes` counts both, and the op wrapper refuses
  shapes that do not fit (an HBM table gathered by DMA is future work);
* the per-column lane state (fin/u_p/u_prev/thr) is tiny [1, W] blocks
  replicated to every grid step;
* accumulation is always fp32; ``table``/``total`` may be stored bf16
  (bf16-storage / fp32-accumulate option) — gathered rows are upcast before
  the inject/prune arithmetic and the outputs cast back on store.

Reduction-order contract: each output row stores its K gathered lanes into
a [K, W] VMEM stack and reduces it with a single ``jnp.sum`` — the same
reduction XLA emits for ``push_ell_padded``'s ``gathered.sum(axis=1)``.
That (not a serial fori-loop accumulate, which XLA reassociates differently
on CPU) is what makes the fused path bitwise-equal to the XLA ELL lane
probe in fp32 on the CPU (tests/test_lane_kernel.py).

Addressing: neighbor ids are GLOBAL node ids.  ``offs = [row0, tab0]`` maps
them into the table: global id x lives at table row ``x - row0 + tab0``.
The local/spmd paths gather from a full frontier (``tab0 == row0``, so the
address is the id itself); the ring path gathers from its own [rows, W]
block (``tab0 == 0``).  Ids >= n_live (ELL sentinel, mesh padding rows)
contribute exact zeros — value masking replaces the dump-row zeroing of the
XLA path, so the kernel needs no [n + 1] buffer convention.
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


def _sublanes(itemsize: int) -> int:
    """Rows one gather reads: 1 for 32-bit storage, else the packed
    dtype's sublane tile (16 rows of bf16)."""
    return 1 if itemsize >= 4 else 8 * (4 // itemsize)


def kernel_bytes(*, block_rows: int, k_slots: int,
                 table_rows: int, width: int, itemsize: int
                 ) -> tuple[int, int]:
    """(VMEM, SMEM) bytes of the kernel's blocks, scratch and prefetched
    operands, sublane-padded."""
    table = _pad(table_rows, max(8, _sublanes(itemsize))) * width * itemsize
    tiles = 4 * 2 * block_rows * width * itemsize  # dep/total in, out/tot out
    lanes = 4 * 2 * 8 * width * 4  # fin/u_p/u_prev/thr [1, W] blocks
    scratch = (_pad(k_slots, 8) + block_rows) * width * 4  # stack + acc
    # id + weight blocks, double-buffered; SMEM pads rows to 128 words
    smem = 2 * block_rows * (_pad(k_slots, 128) + 128) * 4
    return table + tiles + lanes + scratch, smem


def _eff(rows, ids, fin, u_p, thr, *, prune: bool):
    """What a source row sends in one level: a depositing column reads
    zero, the column's walk injects its unit mass at ``u_p``, and pruning
    drops mass at or under the column's threshold.  ``rows`` is fp32
    [r, W], ``ids`` the rows' global node ids (a scalar or [r, W]), the
    lane state [1, W]."""
    eff = jnp.where(fin, 0.0, rows) + (u_p == ids).astype(jnp.float32)
    if prune:
        eff = jnp.where(eff > thr, eff, 0.0)
    return eff


def _kernel(
    offs_ref,    # SMEM int32 [2]     (row0, tab0), scalar prefetch
    nbrs_ref,    # SMEM int32 [bn, K] global neighbor ids of this block
    w_ref,       # SMEM f32   [bn, 1] push weights (already scaled)
    fin_ref,     # int32 [1, W] 1 where the column deposits this level
    up_ref,      # int32 [1, W] injection node id (global; >= n_live: no-op)
    uprev_ref,   # int32 [1, W] exclusion node id (global; >= n_live: no-op)
    thr_ref,     # f32   [1, W] per-column prune threshold
    table_ref,   # [T, W]       gather source (full frontier or own block)
    dep_ref,     # [bn, W]      pre-level scores of this block (deposit src)
    total_ref,   # [bn, W]      per-column accumulator block
    out_ref,     # [bn, W]      pushed scores out
    tot_ref,     # [bn, W]      updated accumulator out
    stack_ref,   # VMEM f32 [K, W]  gathered lanes of one output row
    acc_ref,     # VMEM f32 [bn, W] pushed rows of this block
    *,
    bn: int,
    k_slots: int,
    n_live: int,
    table_rows: int,
    sub: int,
    prune: bool,
):
    pid = pl.program_id(0)
    row0 = offs_ref[0]
    tab0 = offs_ref[1]
    fin = fin_ref[...] != 0
    u_p = up_ref[...]
    u_prev = uprev_ref[...]
    thr = thr_ref[...]

    # deposit: fp32 accumulate, storage-dtype store
    tot = total_ref[...].astype(jnp.float32)
    dep = dep_ref[...].astype(jnp.float32)
    tot_ref[...] = (tot + jnp.where(fin, dep, 0.0)).astype(tot_ref.dtype)

    base_g = row0 + pid * bn  # global node id of this block's row 0

    def row_body(i, carry):
        def k_body(k, carry):
            idx = nbrs_ref[i, k]
            addr = jnp.clip(idx - row0 + tab0, 0, table_rows - 1)
            if sub == 1:
                row = table_ref[pl.ds(addr, 1), :].astype(jnp.float32)
            else:
                # packed dtypes load whole sublane tiles: read the aligned
                # window holding ``addr`` and keep its one row (exact: the
                # other rows add zeros)
                base = pl.multiple_of(addr // sub * sub, sub)
                win = table_ref[pl.ds(base, sub), :].astype(jnp.float32)
                pick = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0)
                row = jnp.sum(jnp.where(pick == addr - base, win, 0.0),
                              axis=0, keepdims=True)
            eff = _eff(row, idx, fin, u_p, thr, prune=prune)
            # sentinel / padding ids contribute exact zeros
            eff = jnp.where(idx >= n_live, 0.0, eff)
            stack_ref[pl.ds(k, 1), :] = eff
            return carry

        jax.lax.fori_loop(0, k_slots, k_body, 0)
        # single jnp.sum over the K stack == XLA's gathered.sum(axis=1)
        row_out = jnp.sum(stack_ref[...], axis=0, keepdims=True) * w_ref[i, 0]
        row_out = jnp.where(u_prev == base_g + i, 0.0, row_out)
        acc_ref[pl.ds(i, 1), :] = row_out
        return carry

    jax.lax.fori_loop(0, bn, row_body, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_live", "prune", "block_rows", "interpret"),
)
def lane_probe_pallas(
    nbrs: Array,     # int32 [R, K]
    weights: Array,  # f32 [R]
    offs: Array,     # int32 [2] = (row0, tab0); may be traced under shard_map
    fin: Array,      # int32 [W]
    u_p: Array,      # int32 [W]
    u_prev: Array,   # int32 [W]
    thr: Array,      # f32 [W]
    table: Array,    # [T, W] storage dtype (f32 or bf16)
    dep: Array,      # [R, W] same dtype as table
    total: Array,    # [R, W] same dtype as table
    *,
    n_live: int,
    prune: bool,
    block_rows: int = 128,
    interpret: bool = True,
) -> tuple[Array, Array]:
    R, K = nbrs.shape
    T, W = table.shape
    assert R % block_rows == 0, f"R={R} must tile by block_rows={block_rows}"
    bn = block_rows
    sub = _sublanes(table.dtype.itemsize)
    if T % sub:  # gather windows never read past the table
        table = jnp.concatenate(
            [table, jnp.zeros((sub - T % sub, W), table.dtype)], axis=0
        )
    kernel = functools.partial(
        _kernel, bn=bn, k_slots=K, n_live=n_live, table_rows=T, sub=sub,
        prune=prune,
    )
    lane = pl.BlockSpec((1, W), lambda i, offs: (0, 0))
    tile = pl.BlockSpec((bn, W), lambda i, offs: (i, 0))
    smem = pltpu.MemorySpace.SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // bn,),
        in_specs=[
            pl.BlockSpec((bn, K), lambda i, offs: (i, 0), memory_space=smem),
            pl.BlockSpec((bn, 1), lambda i, offs: (i, 0), memory_space=smem),
            lane, lane, lane, lane,
            pl.BlockSpec(table.shape, lambda i, offs: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            tile, tile,
        ],
        out_specs=[tile, tile],
        scratch_shapes=[
            pltpu.VMEM((K, W), jnp.float32),
            pltpu.VMEM((bn, W), jnp.float32),
        ],
    )
    need, _ = kernel_bytes(block_rows=bn, k_slots=K, table_rows=T,
                           width=W, itemsize=table.dtype.itemsize)
    out, tot = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, W), table.dtype),
            jax.ShapeDtypeStruct((R, W), total.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(need + (4 << 20), VMEM_LIMIT_BYTES),
        ),
        interpret=interpret,
    )(
        offs, nbrs, weights.reshape(R, 1),
        fin.reshape(1, W), u_p.reshape(1, W), u_prev.reshape(1, W),
        thr.reshape(1, W), table, dep, total,
    )
    return out, tot


# ---------------------------------------------------------------------------
# CSR variant: the same level over a dst-sorted edge list
# ---------------------------------------------------------------------------
#
# The ELL kernel pays n * k_max gathers a level (the table's width is the
# largest in-degree); this one pays one gather per edge.  Its operands are
# a row pointer over the output rows and the source ids in dst order, a
# view the caller derives from the COO push graph once per dispatch.
#
# * The whole fp32 frontier is DMA'd into one VMEM scratch at the first
#   grid step and turned, in place, into what each source row sends this
#   level (``_eff``, once per source row instead of once per edge); every
#   later step gathers rows of it.
# * The row pointer is a scalar-prefetch operand, so each row's edge range
#   is two SMEM reads.
# * The source ids stay in HBM and stream through two SMEM slots of
#   ``chunk`` ids each, in edge order; edge e's id sits at e mod 2*chunk.
#   Moving to a chunk starts the load of the next.  A row whose edges lie
#   in the chunk being read (nearly every row) sums them in one loop; a
#   row that crosses into the next chunk walks its range chunk by chunk,
#   so a hub row of any length reads the same way.  Rows are visited in
#   order and their ranges tile [0, m), so the chunks are visited in order
#   too; the grid therefore runs sequentially ("arbitrary").
# * Edges are gathered ``_UNROLL`` at a time; the last group of a row
#   pads with the buffer's last row, a block-padding row that sends exact
#   zeros, so the buffers hold at least n + 2 rows.
# * Deposit and exclusion are fused as in the ELL kernel; rows past the
#   graph (the dump row and the block padding) carry weight 0 and an
#   empty range, so they are written as exact zeros.

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
