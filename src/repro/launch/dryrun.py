import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell: build the step, jit with
explicit in/out shardings on the production mesh, .lower().compile(), print
memory_analysis + cost_analysis, extract roofline terms (incl. collective
bytes parsed from the partitioned HLO), and append to a JSON manifest.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch smollm-135m
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi            # all cells
"""
import argparse
import json
import time
import traceback

# the production meshes are made of v5e chips; the roofline terms use its
# published peaks (repro.roofline.analysis.DEVICE_PEAKS)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _compile(cell, mesh):
    import jax
    with mesh:
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums or ())
        return jitted.lower(*cell.args).compile()


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             manifest: dict, verbose: bool = True,
             probes: bool = True, overrides: dict | None = None,
             tag: str = "") -> dict:
    from repro.configs.base import get_config
    from repro.launch.steps import build_cell, probe_plan
    from repro.roofline.analysis import (extract_raw, extrapolate_raw,
                                         memory_gb, roofline_from_raw)

    key = f"{arch}/{shape_name}/{mesh_name}" + (f"#{tag}" if tag else "")
    t0 = time.time()
    try:
        cell = build_cell(arch, shape_name, mesh, overrides)
        compiled = _compile(cell, mesh)
        ma = compiled.memory_analysis()
        raw = extract_raw(compiled)
        raw_src = "direct"
        # layered models: XLA cost analysis counts while bodies once ->
        # extract true per-step terms from two loop-free probe compiles
        plan = probe_plan(arch, overrides) if probes else None
        if plan is not None:
            p1 = build_cell(arch, shape_name, mesh, plan[0])
            p2 = build_cell(arch, shape_name, mesh, plan[1])
            r1 = extract_raw(_compile(p1, mesh))
            r2 = extract_raw(_compile(p2, mesh))
            raw = extrapolate_raw(r1, r2, get_config(arch).n_layers)
            raw_src = "probe-extrapolated(L=1,2)"
        roof = roofline_from_raw(raw, arch=arch, shape=shape_name,
                                 mesh_name=mesh_name, n_dev=mesh.size,
                                 model_flops=cell.model_flops,
                                 mem_gb=memory_gb(compiled),
                                 device_kind=TARGET_DEVICE_KIND)
        rec = {
            "status": "ok",
            "kind": cell.kind,
            "raw_source": raw_src,
            "compile_s": round(time.time() - t0, 1),
            "memory_analysis": {
                "argument_gb": round(ma.argument_size_in_bytes / 2**30, 3),
                "output_gb": round(ma.output_size_in_bytes / 2**30, 3),
                "temp_gb": round(ma.temp_size_in_bytes / 2**30, 3),
                "alias_gb": round(ma.alias_size_in_bytes / 2**30, 3),
                "peak_gb": round((ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes) / 2**30, 3),
            },
            "roofline": roof.row(),
        }
        if verbose:
            print(f"[{key}] OK compile={rec['compile_s']}s "
                  f"peak/dev={rec['memory_analysis']['peak_gb']}GB "
                  f"bottleneck={roof.bottleneck} "
                  f"terms(ms)=c{roof.row()['compute_ms']}/m"
                  f"{roof.row()['memory_ms']}/x{roof.row()['collective_ms']} "
                  f"useful={roof.useful_ratio:.2f}", flush=True)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug we record
        rec = {"status": "fail", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:],
               "compile_s": round(time.time() - t0, 1)}
        if verbose:
            print(f"[{key}] FAIL {rec['error']}", flush=True)
    manifest[key] = rec
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--arch", default=None, help="only this arch")
    ap.add_argument("--shape", default=None, help="only this shape")
    ap.add_argument("--out", default="dryrun_manifest.json")
    ap.add_argument("--merge", action="store_true",
                    help="merge into existing manifest instead of overwrite")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="config override key=value (perf iterations), e.g. "
                         "--set causal_skip=true --set score_dtype=bf16")
    ap.add_argument("--tag", default="", help="manifest key suffix")
    args = ap.parse_args()

    overrides = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        elif v in ("bf16", "f32", "fp32", "float32", "bfloat16"):
            import jax.numpy as jnp
            overrides[k] = jnp.bfloat16 if "b" in v else jnp.float32
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v

    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import all_cells

    manifest = {}
    if args.merge and os.path.exists(args.out):
        manifest = json.load(open(args.out))

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single-pod-16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi-pod-2x16x16", make_production_mesh(multi_pod=True)))

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]

    for mesh_name, mesh in meshes:
        for arch, shape_name in cells:
            # roofline probes only needed for the single-pod table
            run_cell(arch, shape_name, mesh, mesh_name, manifest,
                     probes=mesh_name.startswith("single"),
                     overrides=overrides or None, tag=args.tag)
            json.dump(manifest, open(args.out, "w"), indent=1)

    ok = sum(1 for v in manifest.values() if v.get("status") == "ok")
    print(f"\n{ok}/{len(manifest)} cells OK -> {args.out}")
    if ok < len(manifest):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
