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
            # deposit-zeroing + injection, per gathered element
            eff = jnp.where(fin, 0.0, row) + (u_p == idx).astype(jnp.float32)
            if prune:
                eff = jnp.where(eff > thr, eff, 0.0)
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
