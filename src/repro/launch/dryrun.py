import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile EVERY (architecture × input shape)
on the production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST run before any other import: jax locks the
device count on first init.  512 host devices back both the (16,16)
single-pod and the (2,16,16) multi-pod meshes (the single-pod run uses
the first 256).
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import REGISTRY, get_arch            # noqa: E402
from repro.launch.mesh import (HBM_PER_CHIP, make_production_mesh,
                               make_rules)              # noqa: E402
from repro.launch import roofline                       # noqa: E402


def run_cell(arch_name: str, shape: str, multi_pod: bool,
             extract_hlo: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh)
    chips = mesh.devices.size
    arch = get_arch(arch_name)
    builder = arch.cells[shape]
    t0 = time.time()
    prog = builder(mesh, rules)
    with jax.set_mesh(mesh):
        jitted = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                         donate_argnums=prog.donate_argnums)
        lowered = jitted.lower(*prog.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    per_dev = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        "generated_code_bytes": int(
            getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    # peak live bytes per device (args + outputs + temps − donated aliases)
    peak = (per_dev["argument_bytes"] + per_dev["output_bytes"]
            + per_dev["temp_bytes"] - per_dev["alias_bytes"])
    hlo = compiled.as_text() if extract_hlo else ""
    terms = roofline.analyze(compiled, hlo, chips)
    # CPU float-normalization shadows (f32 copies of bf16 stacks) do not
    # exist on TPU — report an adjusted peak too (see hlo_cost docstring).
    from repro.launch.hlo_cost import estimate_f32_shadow_bytes
    shadow = estimate_f32_shadow_bytes(hlo) if hlo else 0
    peak_adj = max(peak - shadow, per_dev["argument_bytes"])
    mf = prog.model_flops_per_step          # GLOBAL model flops per step
    mf_dev = mf / chips if chips else mf    # per-device share
    result = {
        "arch": arch_name, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "description": prog.description,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_per_device": per_dev,
        "peak_bytes_per_device": int(peak),
        "cpu_f32_shadow_bytes": int(shadow),
        "peak_adjusted_bytes": int(peak_adj),
        "fits_16GiB": bool(peak <= HBM_PER_CHIP),
        "fits_16GiB_adjusted": bool(peak_adj <= HBM_PER_CHIP),
        "roofline": terms.as_dict(),
        "model_flops": mf,
        "model_flops_per_device": mf_dev,
        "useful_flops_ratio": (mf_dev / terms.flops) if terms.flops else None,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for name, arch in REGISTRY.items():
            for shape in arch.cells:
                cells.append((name, shape))
    else:
        arch = get_arch(args.arch)
        shapes = [args.shape] if args.shape else list(arch.cells)
        cells = [(args.arch, s) for s in shapes]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for name, shape in cells:
        for mp in meshes:
            tag = f"{name} × {shape} × {'2x16x16' if mp else '16x16'}"
            try:
                r = run_cell(name, shape, mp)
                rt = r["roofline"]
                print(f"[OK] {tag}: peak={r['peak_bytes_per_device']/2**30:.2f}GiB "
                      f"adj={r['peak_adjusted_bytes']/2**30:.2f}GiB "
                      f"fits={r['fits_16GiB_adjusted']} "
                      f"t_comp={rt['t_compute_s']:.2e}s "
                      f"t_mem={rt['t_memory_s']:.2e}s "
                      f"t_coll={rt['t_collective_s']:.2e}s "
                      f"bound={rt['bottleneck']} "
                      f"(compile {r['compile_s']}s)", flush=True)
            except Exception as e:  # noqa: BLE001
                r = {"arch": name, "shape": shape,
                     "mesh": "2x16x16" if mp else "16x16",
                     "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            results.append(r)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print(f"wrote {args.out}")
    n_ok = sum(1 for r in results if "error" not in r)
    print(f"{n_ok}/{len(results)} cells passed")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
