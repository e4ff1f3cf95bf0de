import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract roofline inputs from the compiled artifact.

MUST be run as its own process (`python -m repro.launch.dryrun ...`): the
first two lines above pin 512 placeholder host devices before any jax
import, which is process-global.

Per cell it records:
  * memory_analysis()  — per-device bytes (proves the config fits HBM)
  * cost_analysis()    — HLO FLOPs / bytes accessed
  * collective operand bytes by kind, parsed from the post-SPMD HLO text
and writes experiments/dryrun/<mesh>/<arch>__<shape>.json.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.analysis.hlo import collective_bytes, hlo_op_histogram  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import all_cells, make_run_config  # noqa: E402
from repro.launch.steps import build_step  # noqa: E402


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: str,
             rc_overrides=None, tag: str = "") -> dict:
    mesh_name = "pod512" if multi_pod else "pod256"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rcfg = make_run_config(arch, shape, **(rc_overrides or {}))
    with jax.set_mesh(mesh):
        jitted, arg_shapes, _shardings = build_step(mesh, rcfg.model, rcfg)
        lowered = jitted.lower(*arg_shapes.values())
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()

    coll = collective_bytes(hlo_text)
    from repro.analysis.hlo_cost import module_cost
    parsed = module_cost(hlo_text)
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "mode": rcfg.mode,
        "quant": rcfg.quant.scheme if rcfg.quant else "bf16",
        "quant_impl": rcfg.quant.impl if rcfg.quant else None,
        "devices": int(len(mesh.devices.flat)),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1.0)),
        # trip-count-aware parsed totals (XLA cost_analysis counts while
        # bodies once; these multiply through the loop nest — see
        # analysis/hlo_cost.py):
        "parsed_flops": parsed.flops,
        "parsed_hbm_bytes": parsed.hbm_bytes,
        "parsed_collectives": dict(parsed.collectives),
        "parsed_traffic": dict(parsed.traffic),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", -1)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", -1)),
        },
        "collectives": coll,
        "hlo_ops": hlo_op_histogram(hlo_text),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    with open(os.path.join(d, f"{arch}__{shape}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--quant-scheme", default=None)
    ap.add_argument("--quant-impl", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
        if not cells:  # non-assigned archs (e.g. the paper's qwen2.5-7b)
            from repro.configs import get_config
            from repro.launch.specs import shapes_for
            cells = [(args.arch, s) for s in shapes_for(get_config(args.arch))]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    overrides = {}
    if args.quant_scheme or args.quant_impl:
        from repro.launch.specs import DEFAULT_SERVE_QUANT
        import dataclasses as dc
        q = DEFAULT_SERVE_QUANT
        if args.quant_scheme:
            q = dc.replace(q, scheme=args.quant_scheme)
        if args.quant_impl:
            q = dc.replace(q, impl=args.quant_impl)
        overrides["quant"] = q

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod512" if mp else "pod256"
            path = os.path.join(args.out, mesh_name,
                                f"{arch}__{shape}{('__' + args.tag) if args.tag else ''}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {arch} {shape} {mesh_name}")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                               rc_overrides=overrides, tag=args.tag)
                print(f"[ok] {arch:24s} {shape:12s} {mesh_name}  "
                      f"flops/dev={rec['flops_per_device']:.3e}  "
                      f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB  "
                      f"compile={rec['compile_s']}s", flush=True)
            except Exception as e:
                failures.append((arch, shape, mesh_name, repr(e)))
                print(f"[FAIL] {arch} {shape} {mesh_name}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
