"""Kernel micro-benchmarks — a promoted structured suite (PR 10).

Three legs:

* the spmm_ell oracle timing + telescoped-vs-per-prefix probe win
  (unchanged CSV rows from the original suite);
* the fused lane-probe level kernel vs the XLA lane-level oracle at LEVEL
  granularity (one deposit+inject+prune+push+exclude pass over a [R, K]
  ELL block and [T, W] score table) — ``fused_vs_xla_speedup`` is the
  ratio CI gates on.  On CPU the kernel runs in interpret mode, so the
  ratio is an availability/parity check there (< 1 is expected); on TPU
  it is the real fused-vs-scatter speedup;
* on a TPU, a roofline record for BOTH programs via
  ``roofline/analysis.py`` (per-device HLO FLOPs/bytes from
  ``compiled.cost_analysis()`` against the running chip's published peaks,
  ``launch.mesh.peaks``, plus the ideal model FLOPs/bytes of the level so
  achieved-vs-ideal ratios are in the artifact).  Elsewhere there is no
  chip to hold to a peak, and the record is left out.

Exports ``RESULTS["kernels"]`` and (via run.py) ``BENCH_kernels.json``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import RESULTS, emit, timed
from repro.api import GraphHandle
from repro.core import estimate_walk_reference, probe_walks_telescoped, sample_walks
from repro.graph import powerlaw_graph
from repro.kernels.spmm_ell.ref import spmm_ell_ref


def _lane_level_operands(rng, *, r, k, w):
    """A random mid-probe level: live and finished lanes, injections,
    sentinel ELL slots — the shapes the serve path dispatches."""
    t = r + 1  # local layout: score table carries the sentinel dump row
    nbrs = jnp.asarray(rng.integers(0, r + 1, (r, k)).astype(np.int32))
    weights = jnp.asarray(rng.uniform(0.1, 1, r).astype(np.float32))
    table = jnp.asarray(rng.random((t, w)).astype(np.float32))
    dep = jnp.asarray(rng.random((r, w)).astype(np.float32))
    total = jnp.asarray(rng.random((r, w)).astype(np.float32))
    fin = jnp.asarray(rng.random(w) < 0.3)
    u_p = jnp.asarray(np.where(rng.random(w) < 0.5,
                               rng.integers(0, r, w), r).astype(np.int32))
    u_prev = jnp.asarray(np.where(rng.random(w) < 0.5,
                                  rng.integers(0, r, w), r).astype(np.int32))
    thr = jnp.asarray((rng.random(w) * 1e-3).astype(np.float32))
    return nbrs, weights, table, dep, total, fin, u_p, u_prev, thr


def _lane_probe_leg(quick: bool) -> None:
    from repro.kernels.lane_probe.ops import _on_tpu, lane_probe_level
    from repro.kernels.lane_probe.ref import lane_probe_level_ref
    from repro.launch.mesh import peaks
    from repro.roofline.analysis import analyze

    rng = np.random.default_rng(0)
    r, k, w = (512, 8, 128) if quick else (4096, 16, 256)
    args = _lane_level_operands(rng, r=r, k=k, w=w)

    fused = jax.jit(
        lambda *a: lane_probe_level(*a, row0=0, tab0=0, n_live=r, prune=True)
    )
    oracle = jax.jit(
        lambda *a: lane_probe_level_ref(
            *a, row0=0, tab0=0, n_live=r, prune=True
        )
    )
    reps = 5 if quick else 10
    (out_f, _), t_fused = timed(fused, *args, reps=reps)
    (out_x, _), t_xla = timed(oracle, *args, reps=reps)
    assert np.array_equal(np.asarray(out_f), np.asarray(out_x)), \
        "fused kernel diverged from the XLA oracle"
    mode = "compiled" if _on_tpu() else "interpret"
    speedup = t_xla / max(t_fused, 1e-12)
    shape = f"r{r}_k{k}_w{w}"
    emit(f"kernel/lane_probe_fused_{mode}", t_fused * 1e6, f"shape={shape}")
    emit("kernel/lane_probe_xla_oracle", t_xla * 1e6,
         f"shape={shape};fused_vs_xla_speedup={speedup:.3f}x")

    # roofline: both programs against the chip's peaks. Ideal terms for one
    # level: 2 flops per (row, slot, lane) gather-accumulate plus the
    # weight multiply/exclusion, and one pass over every operand/result.
    model_flops = 2.0 * r * k * w + 2.0 * r * w
    ideal_bytes = 4.0 * (
        r * k              # nbrs (int32)
        + r                # weights
        + r * k * w        # gathered table rows (no-reuse upper bound)
        + 4 * r * w        # dep + total in, scores + total out
        + 4 * w            # lane vectors
    )
    roofline = {}
    hw = peaks(jax.devices()[0].device_kind) if _on_tpu() else None
    for name, fn in ((("fused", fused), ("xla", oracle)) if hw else ()):
        compiled = fn.lower(*args).compile()
        rep = analyze(
            arch=f"lane_probe_{name}", shape=shape, mesh_name="single",
            chips=1, compiled=compiled, model_flops=model_flops, hw=hw,
        )
        d = rep.to_dict()
        d["ideal_bytes"] = ideal_bytes
        d["bytes_vs_ideal"] = (
            rep.hlo_bytes / ideal_bytes if ideal_bytes else 0.0
        )
        roofline[name] = d
        emit(f"kernel/lane_probe_roofline_{name}",
             (rep.compute_s + rep.memory_s) * 1e6,
             f"bottleneck={rep.bottleneck};"
             f"flops_vs_ideal={rep.hlo_flops / model_flops:.2f};"
             f"bytes_vs_ideal={d['bytes_vs_ideal']:.2f}")

    RESULTS["kernels"] = dict(
        backend=jax.default_backend(),
        mode=mode,
        shape=dict(rows=r, k_slots=k, lanes=w),
        fused_us=t_fused * 1e6,
        xla_us=t_xla * 1e6,
        fused_vs_xla_speedup=speedup,
        model_flops=model_flops,
        ideal_bytes=ideal_bytes,
        roofline=roofline,
    )


def run(quick: bool = True) -> None:
    rng = np.random.default_rng(0)
    n, K, B = (1024, 8, 64) if quick else (8192, 16, 128)
    nbrs = jnp.asarray(rng.integers(0, n + 1, (n, K)).astype(np.int32))
    scores = jnp.asarray(rng.normal(size=(n, B)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 1, n).astype(np.float32))
    ref_jit = jax.jit(spmm_ell_ref)
    _, t_ref = timed(ref_jit, nbrs, scores, w, reps=10)
    emit("kernel/spmm_ell_oracle", t_ref * 1e6,
         f"n={n};K={K};B={B};note=pallas_interpret_on_cpu_not_timed")

    _lane_probe_leg(quick)

    # algorithmic win: telescoped O(l) vs per-prefix O(l^2) pushes
    src, dst, gn = powerlaw_graph(2000, 16_000, seed=1)
    h = GraphHandle.from_edges(src, dst, gn)
    u = int(dst[0])
    walks = sample_walks(jax.random.key(0), h.eg, u, n_r=32, max_len=10,
                         sqrt_c=0.775)
    _, t_tel = timed(
        probe_walks_telescoped, h.g, walks, sqrt_c=0.775, reps=3
    )

    def per_prefix_all():
        outs = []
        for k in range(8):  # subset: reference is the slow oracle
            outs.append(estimate_walk_reference(h.g, walks[k], 0.775))
        return outs

    _, t_ref_probe = timed(per_prefix_all)
    t_ref_scaled = t_ref_probe * (32 / 8)
    emit("probe/telescoped_32walks", t_tel * 1e6, "pushes=L-1_per_batch")
    emit("probe/per_prefix_32walks_est", t_ref_scaled * 1e6,
         f"speedup={t_ref_scaled / max(t_tel, 1e-9):.1f}x")


if __name__ == "__main__":
    run(quick=False)
