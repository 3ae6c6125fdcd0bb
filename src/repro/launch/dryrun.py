"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh)
cell and extract the roofline terms.

Run as a script, it appends ``--xla_force_host_platform_device_count=512``
to ``XLA_FLAGS`` before jax initializes its backend (jax locks the
device count on first init, and the production meshes need 512
host-platform placeholder devices).  Importing the module changes no
environment: everything else (tests, benches, examples) sees the real
devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch all|<id>[,<id>...]] [--shape all|<name>] \
      [--mesh both|single|multi] [--out results/dryrun.json]

Results stream to the JSON file incrementally; rerunning skips cells
already present (resumable -- each compile is minutes of CPU work).
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import ALL_ARCHS
from repro.launch import hlo_analysis, roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.base import SHAPES, get_arch

GIB = 2.0 ** 30


def run_cell(arch: str, shape: str, mesh_name: str, *,
             rule_overrides=None, microbatches=None, remat=None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    t0 = time.time()
    plan = build_cell(arch, shape, mesh, rule_overrides=rule_overrides,
                      microbatches=microbatches, remat=remat)
    lowered = plan.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    memory_gb = {
        "args": ma.argument_size_in_bytes / GIB,
        "out": ma.output_size_in_bytes / GIB,
        "temp": ma.temp_size_in_bytes / GIB,
        "alias": ma.alias_size_in_bytes / GIB,
        "peak": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes
                 - ma.alias_size_in_bytes) / GIB,
    }
    xla_costs = compiled.cost_analysis()
    costs = hlo_analysis.analyze(compiled.as_text())
    row = roofline.build_row(arch, shape, mesh_name, chips, costs,
                             memory_gb)
    out = row.to_json()
    out.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "xla_flops_unweighted": xla_costs.get("flops", 0.0),
        "xla_bytes_unweighted": xla_costs.get("bytes accessed", 0.0),
        "microbatches": plan.microbatches,
    })
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["both", "single", "multi"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ALL_ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    for arch in archs:
        bundle = get_arch(arch)
        for shape in shapes:
            for mesh_name in meshes:
                key = f"{arch}|{shape}|{mesh_name}"
                if key in results and results[key].get("status") in (
                        "ok", "skipped"):
                    print(f"[cached] {key}")
                    continue
                if shape in bundle.skip_cells:
                    results[key] = {
                        "status": "skipped",
                        "reason": bundle.skip_reasons.get(shape, "")}
                    print(f"[skip]   {key}: "
                          f"{bundle.skip_reasons.get(shape, '')[:60]}")
                else:
                    print(f"[run]    {key} ...", flush=True)
                    try:
                        results[key] = run_cell(arch, shape, mesh_name)
                        r = results[key]
                        print(f"  ok: peak {r['memory_gb']['peak']:.1f} GiB"
                              f"/chip, bottleneck {r['bottleneck']}, "
                              f"compile {r['compile_s']}s", flush=True)
                    except Exception as e:   # noqa: BLE001
                        results[key] = {"status": "failed",
                                        "error": f"{type(e).__name__}: {e}",
                                        "trace": traceback.format_exc()[-2000:]}
                        print(f"  FAILED: {e}", flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in results.values()
                 if r.get("status") == "skipped")
    n_fail = sum(1 for r in results.values()
                 if r.get("status") == "failed")
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))
    main()
