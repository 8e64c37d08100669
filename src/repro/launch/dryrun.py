import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           # XLA *CPU* crashes cloning bf16 all-reduces in the
                           # AllReducePromotion pass (hlo_instruction.cc:1558,
                           # "Invalid binary instruction opcode copy"); the
                           # pass is a CPU-only numerics shim and we only
                           # lower+compile here, never execute.  Irrelevant on
                           # real TPU backends.
                           "--xla_disable_hlo_passes=all-reduce-promotion")

"""Multi-pod dry-run: .lower().compile() every (architecture x input shape)
on the production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun                 # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape train_4k --mesh single --train-mode shared_server

Results land in experiments/dryrun/<arch>__<shape>__<mesh>[__mode].json and
are aggregated by benchmarks/roofline_table.py into EXPERIMENTS.md §Roofline.

NOTE: the XLA_FLAGS line above MUST run before any other import (jax locks
the device count at first init); that is why it is the first statement.
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.registry import ARCHS, get_arch, supports_shape
from repro.configs.shapes import SHAPES
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh, num_chips
from repro.launch.steps import build_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_one(arch: str, shape_name: str, mesh_name: str, *,
            train_mode: str = "paper_faithful",
            serve_param_mode: str = "fsdp_tp", agg_dtype: str = "float32",
            remat: bool = True, remat_policy: str = "full",
            local_steps: int | None = None,
            out_dir: str = OUT_DIR, verbose: bool = True) -> dict:
    from repro.configs.base import TrainConfig

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if mesh_name == "alt32x8":
        from repro.launch.mesh import make_alt_mesh
        mesh = make_alt_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = num_chips(mesh)
    tcfg = None
    if (agg_dtype != "float32" or not remat or local_steps is not None
            or remat_policy != "full"):
        tcfg = TrainConfig(agg_dtype=agg_dtype, remat=remat,
                           remat_policy=remat_policy,
                           local_steps_in_step=local_steps or 2)
    t0 = time.time()
    with jax.set_mesh(mesh):
        bundle = build_step(cfg, shape, mesh, train_mode=train_mode,
                            serve_param_mode=serve_param_mode, tcfg=tcfg)
        lowered = jax.jit(bundle.fn).lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem_report = ""
        try:
            mem_report = str(compiled.memory_analysis())
        except Exception as e:  # CPU backend may not support it fully
            mem_report = f"<memory_analysis unavailable: {e}>"

        roof = rf.analyze(compiled, None, arch=arch, shape=shape,
                          mesh_name=mesh_name, chips=chips, kind=shape.kind,
                          cfg=cfg, mesh_shape=dict(mesh.shape),
                          mode=train_mode, param_mode=serve_param_mode,
                          agg_dtype_bytes=(2 if agg_dtype == "bfloat16"
                                           else 4), tcfg=tcfg)

    rec = roof.to_dict()
    rec.update({"train_mode": train_mode if shape.kind == "train" else None,
                "step_meta": bundle.meta, "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1),
                "memory_analysis": mem_report})
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{train_mode}" if (shape.kind == "train"
                                   and train_mode != "paper_faithful") else ""
    if shape.kind in ("decode", "prefill") and serve_param_mode != "fsdp_tp":
        suffix += f"__{serve_param_mode}"
    if shape.kind == "train" and agg_dtype != "float32":
        suffix += f"__agg{agg_dtype}"
    if shape.kind == "train" and not remat:
        suffix += "__noremat"
    if shape.kind == "train" and remat_policy != "full":
        suffix += f"__remat_{remat_policy}"
    if shape.kind == "train" and local_steps is not None:
        suffix += f"__k{local_steps}"
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        print(f"[dryrun] {arch:24s} {shape_name:12s} {mesh_name:8s} "
              f"ok chips={chips} "
              f"compute={roof.compute_s:.3e}s memory={roof.memory_s:.3e}s "
              f"collective={roof.collective_s:.3e}s dominant={roof.dominant} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
        print(f"  memory_analysis: {mem_report[:300]}", flush=True)
        print(f"  analytic: flops/chip={roof.flops:.3e} bytes/chip="
              f"{roof.hbm_bytes:.3e} coll_bytes/chip={roof.coll_bytes:.3e} "
              f"useful_flops_ratio={roof.useful_flops_ratio:.3f}", flush=True)
        print(f"  hlo(loop-bodies-once): flops={roof.hlo_flops:.3e} "
              f"bytes={roof.hlo_bytes:.3e} coll={roof.hlo_coll_bytes:.3e} "
              f"counts={roof.coll_detail.get('counts')}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default all)")
    ap.add_argument("--shape", default=None, help="input shape (default all)")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multipod", "both", "alt32x8"])
    ap.add_argument("--train-mode", default="paper_faithful",
                    choices=["paper_faithful", "shared_server"])
    ap.add_argument("--serve-params", default="fsdp_tp",
                    choices=["fsdp_tp", "tp"],
                    help="decode weight residency: fsdp (all-gather/step) "
                         "or tp-resident")
    ap.add_argument("--agg-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="hierarchical aggregation psum dtype")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-block activation checkpointing")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"],
                    help="checkpoint policy: full recompute vs save-dots")
    ap.add_argument("--local-steps", type=int, default=None,
                    help="kappa0 local steps fused per round call")
    ap.add_argument("--out-dir", default=OUT_DIR)
    ap.add_argument("--keep-going", action="store_true",
                    help="continue past failures (collect all errors)")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    n_ok = n_skip = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                if not supports_shape(arch, shape_name):
                    print(f"[dryrun] {arch:24s} {shape_name:12s} {mesh_name:8s} "
                          f"SKIP (long-context requires sub-quadratic mixing; "
                          f"see DESIGN.md)", flush=True)
                    n_skip += 1
                    continue
                try:
                    run_one(arch, shape_name, mesh_name,
                            train_mode=args.train_mode,
                            serve_param_mode=args.serve_params,
                            agg_dtype=args.agg_dtype,
                            remat=not args.no_remat,
                            remat_policy=args.remat_policy,
                            local_steps=args.local_steps,
                            out_dir=args.out_dir)
                    n_ok += 1
                except Exception as e:
                    failures.append((arch, shape_name, mesh_name, repr(e)))
                    print(f"[dryrun] {arch} {shape_name} {mesh_name} FAILED: {e}",
                          flush=True)
                    if not args.keep_going:
                        traceback.print_exc()
                        sys.exit(1)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {len(failures)} failed",
          flush=True)
    if failures:
        for f in failures:
            print("  FAIL:", *f)
        sys.exit(1)


if __name__ == "__main__":
    main()
