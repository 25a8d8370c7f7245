import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
512 placeholder host devices, record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Skipped cells (long_500k on full-attention archs) are recorded with their
reason so the 40-cell table in EXPERIMENTS.md is complete.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.core.paths import results_dir
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_case
from repro.parallel.collectives import parse_collective_bytes
from repro import costmodel, roofline


def _mem_analysis_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             microbatches: int = 1, fsdp: bool = True, dp_only: bool = False,
             param_dtype: str | None = None,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "microbatches": microbatches, "fsdp": fsdp, "dp_only": dp_only}
    ok, reason = applicable(cfg, shape)
    if not ok:
        cell.update(status="skipped", reason=reason)
        return cell

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    case = build_case(arch, shape_name, mesh, multi_pod=multi_pod,
                      microbatches=microbatches, fsdp=fsdp, dp_only=dp_only,
                      param_dtype=param_dtype)
    try:
        jitted = jax.jit(case["fn"], in_shardings=case["in_shardings"],
                         donate_argnums=case["donate"])
        lowered = jitted.lower(*case["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failure here is a bug in the system
        cell.update(status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-4000:])
        return cell

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo, mesh.size)
    mflops = roofline.model_flops(cfg, shape)
    # scan-exact jaxpr cost (XLA's cost_analysis counts loop bodies once —
    # see DESIGN.md / tests/test_costmodel.py); global -> per chip
    cm = costmodel.cost_of(case["fn"], *case["args"])
    cost = {"flops": cm.total_flops / mesh.size,
            # fusion-aware traffic (scan boundaries = kernel boundaries;
            # VMEM-resident intermediates excluded — the schedule the Pallas
            # kernels implement). cm.bytes (no-fusion upper bound) is kept
            # in cost_detail for comparison.
            "bytes accessed": cm.bytes_fused / mesh.size}
    terms = roofline.terms_from_analysis(cost, coll.per_chip_link_bytes,
                                         mesh.size, mflops)
    cell.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=_mem_analysis_dict(mem),
        cost=cost,
        cost_detail=cm.as_dict(),
        xla_cost={k: xla_cost[k] for k in ("flops", "bytes accessed")
                  if k in xla_cost},
        collectives=coll.as_dict(),
        roofline=terms.as_dict(),
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        hlo_bytes=len(hlo),
    )
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"compile={t_compile:.1f}s dominant={terms.dominant} "
              f"mfu~{terms.mfu:.3f}")
        print("  memory_analysis:", cell["memory"])
        print("  cost_analysis: flops/chip=%.3e bytes/chip=%.3e"
              % (terms.flops_per_chip, terms.bytes_per_chip))
        print("  collectives:", {k: v["count"]
                                 for k, v in coll.by_kind.items()})
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None,
                    help="output dir (default: $REPRO_RESULTS_DIR/dryrun)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--param-dtype", choices=("fp8", "bf16", "f32"),
                    default=None)
    args = ap.parse_args()

    if args.out is None:
        args.out = results_dir("dryrun")
    os.makedirs(args.out, exist_ok=True)
    arches = ARCH_IDS if args.all or not args.arch else (args.arch,)
    shapes = tuple(SHAPES) if args.all or not args.shape else (args.shape,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    n_err = 0
    for arch in arches:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.microbatches != 1:
                    tag += f"__mb{args.microbatches}"
                if args.no_fsdp:
                    tag += "__nofsdp"
                if args.dp_only:
                    tag += "__dponly"
                if args.param_dtype:
                    tag += f"__{args.param_dtype}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[{tag}] cached")
                    continue
                cell = run_cell(arch, shape, mp,
                                microbatches=args.microbatches,
                                fsdp=not args.no_fsdp,
                                dp_only=args.dp_only,
                                param_dtype=args.param_dtype)
                if cell["status"] == "error":
                    n_err += 1
                    print(f"[{tag}] ERROR: {cell['error']}")
                elif cell["status"] == "skipped":
                    print(f"[{tag}] SKIPPED: {cell['reason'][:80]}")
                with open(path, "w") as f:
                    json.dump(cell, f, indent=1)
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
