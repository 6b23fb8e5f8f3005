import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST stay first: jax locks the device count on first
# initialization (which is why there is no `from __future__` here).

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape) cell on the 16x16 single-pod mesh and the
2x16x16 multi-pod mesh, print memory/cost analysis, and emit the roofline
records consumed by EXPERIMENTS.md.

The two lines above MUST stay first: jax locks the device count on first
initialization.

Methodology notes (see roofline/analysis.py):
* cost_analysis() is per-device and counts while bodies ONCE; scanned layer
  stacks are therefore measured by depth-delta extrapolation: compile the
  model at two small depths, extrapolate linearly per homogeneous stage
  (exact for scanned stacks), and take memory_analysis from the full-depth
  compile.
* collective bytes are parsed from optimized HLO with while-trip weighting.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import arch as arch_mod
from repro.configs.base import ARCH_IDS, get_config, shapes_for
from repro.launch.mesh import HW, make_production_mesh
from repro.roofline import analysis as ra
from repro.utils.jaxcompat import specs_to_shardings


def abstract_state(bundle):
    """State as ShapeDtypeStructs without allocating anything."""
    try:
        return jax.eval_shape(bundle.init, jax.random.key(0))
    except Exception:
        # init already returns ShapeDtypeStructs (probesim at full scale)
        return bundle.init(jax.random.key(0))


def lower_and_compile(bundle, mesh):
    with jax.set_mesh(mesh):
        state = abstract_state(bundle)
        state_specs = bundle.state_specs(state)
        in_shard = bundle.input_shardings()
        inputs = bundle.input_specs()
        input_order = list(inputs)
        jf = jax.jit(
            bundle.step,
            in_shardings=specs_to_shardings(
                (*state_specs, *(in_shard[k] for k in input_order)), mesh=mesh
            ),
        )
        t0 = time.time()
        lowered = jf.lower(*state, *(inputs[k] for k in input_order))
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    return compiled, dict(lower_s=t1 - t0, compile_s=t2 - t1)


def _depth_variants(cfg):
    """Two reduced-depth configs for delta extrapolation (per stage)."""
    if cfg.family != "lm":
        return None
    fd = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    k1 = fd + 2
    k2 = fd + 3
    if cfg.n_layers <= k2:  # already shallow: no extrapolation needed
        return None
    # unrolled so cost_analysis sees every layer (scan bodies count once)
    mk = lambda k: dataclasses.replace(cfg, n_layers=k, scan_layers=False)
    return (k1, mk(k1)), (k2, mk(k2))


def run_cell(arch_id: str, shape_name: str, mesh_name: str, *,
             skip_full_compile: bool = False,
             overrides: dict | None = None) -> dict:
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = 512 if multi else 256
    applicable, why = arch_mod.is_applicable(arch_id, shape_name)
    record: dict = dict(arch=arch_id, shape=shape_name, mesh=mesh_name,
                        chips=chips, applicable=applicable)
    if not applicable:
        record["skip_reason"] = why
        # still attempt the compile as a bonus cell
    bundle = arch_mod.build(arch_id, shape_name)
    if overrides:
        top = {k: v for k, v in overrides.items() if "." not in k}
        moe_over = {k.split(".", 1)[1]: v for k, v in overrides.items()
                    if k.startswith("moe.")}
        cfg_o = dataclasses.replace(bundle.cfg, **top)
        if moe_over:
            cfg_o = dataclasses.replace(
                cfg_o, moe=dataclasses.replace(cfg_o.moe, **moe_over))
        # mesh context needed for probesim shard-count-dependent init
        with jax.set_mesh(mesh):
            bundle = arch_mod.build_with_cfg(arch_id, cfg_o, bundle.shape)
        record["overrides"] = {k: str(v) for k, v in overrides.items()}
    cfg = bundle.cfg

    variants = _depth_variants(cfg)
    if variants is None:
        compiled, times = lower_and_compile(bundle, mesh)
        rep = ra.analyze(
            arch=arch_id, shape=shape_name, mesh_name=mesh_name, chips=chips,
            compiled=compiled, model_flops=bundle.model_flops(), hw=HW,
        )
        record.update(rep.to_dict(), **times)
        return record

    # depth-delta extrapolation for scanned LM stacks
    (k1, cfg1), (k2, cfg2) = variants
    shape = bundle.shape
    b1 = arch_mod.build_with_cfg(arch_id, cfg1, shape)
    b2 = arch_mod.build_with_cfg(arch_id, cfg2, shape)
    c1, t1 = lower_and_compile(b1, mesh)
    c2, t2 = lower_and_compile(b2, mesh)
    r1 = ra.analyze(arch=arch_id, shape=shape_name, mesh_name=mesh_name,
                    chips=chips, compiled=c1, model_flops=0.0, hw=HW)
    r2 = ra.analyze(arch=arch_id, shape=shape_name, mesh_name=mesh_name,
                    chips=chips, compiled=c2, model_flops=0.0, hw=HW)
    L = cfg.n_layers
    ext = lambda a, b: a + (b - a) * (L - k1) / (k2 - k1)
    rep = ra.RooflineReport(
        arch=arch_id, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=ext(r1.hlo_flops, r2.hlo_flops),
        hlo_bytes=ext(r1.hlo_bytes, r2.hlo_bytes),
        collective_bytes=ext(r1.collective_bytes, r2.collective_bytes),
        model_flops=bundle.model_flops(),
        collectives=dict(
            by_kind={
                k: ext(r1.collectives["by_kind"][k], r2.collectives["by_kind"][k])
                for k in r1.collectives["by_kind"]
            },
            counts=r2.collectives["counts"],
            total_bytes=ext(r1.collectives["total_bytes"],
                            r2.collectives["total_bytes"]),
        ),
    ).finalize(HW)
    record.update(rep.to_dict())
    record["extrapolated_from_depths"] = [k1, k2]
    record["lower_s"] = t1["lower_s"] + t2["lower_s"]
    record["compile_s"] = t1["compile_s"] + t2["compile_s"]

    if not skip_full_compile:
        # full-depth compile: proves the real cell compiles + true memory
        compiled, times = lower_and_compile(bundle, mesh)
        ma = compiled.memory_analysis()
        if ma is not None:
            record["memory_per_device"] = dict(
                argument_gb=ma.argument_size_in_bytes / 1e9,
                output_gb=ma.output_size_in_bytes / 1e9,
                temp_gb=ma.temp_size_in_bytes / 1e9,
                alias_gb=ma.alias_size_in_bytes / 1e9,
            )
        record["full_compile_s"] = times["compile_s"]
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-full-compile", action="store_true",
                    help="skip the full-depth compile (faster iteration)")
    ap.add_argument("--include-skipped", action="store_true",
                    help="also run inapplicable cells as bonus compiles")
    ap.add_argument("--set", nargs="*", default=[], metavar="K=V",
                    help="config overrides, e.g. push_mode=ring remat=False")
    ap.add_argument("--tag", default="",
                    help="suffix for output filenames (perf iterations)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        elif v.isdigit():
            v = int(v)
        else:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in shapes_for(a):
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for a, s in cells:
        applicable, why = arch_mod.is_applicable(a, s)
        if not applicable and not args.include_skipped:
            print(f"SKIP {a} x {s}: {why}")
            rec = dict(arch=a, shape=s, applicable=False, skip_reason=why)
            with open(os.path.join(args.out, f"{a}__{s}__skip.json"), "w") as f:
                json.dump(rec, f, indent=2)
            continue
        for m in meshes:
            tag = f"{a}__{s}__{m}" + (f"__{args.tag}" if args.tag else "")
            t0 = time.time()
            try:
                rec = run_cell(a, s, m, skip_full_compile=args.skip_full_compile,
                               overrides=overrides or None)
                rec["wall_s"] = time.time() - t0
                with open(os.path.join(args.out, f"{tag}.json"), "w") as f:
                    json.dump(rec, f, indent=2, default=float)
                print(
                    f"OK   {tag}: flops/dev={rec.get('hlo_flops', 0):.3e} "
                    f"coll/dev={rec.get('collective_bytes', 0):.3e}B "
                    f"bottleneck={rec.get('bottleneck', '?')} "
                    f"({rec['wall_s']:.0f}s)"
                )
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {e}")
                traceback.print_exc()
                with open(os.path.join(args.out, f"{tag}.FAILED.json"), "w") as f:
                    json.dump(dict(arch=a, shape=s, mesh=m, error=str(e)), f)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")
    print("dry-run complete")


if __name__ == "__main__":
    main()
