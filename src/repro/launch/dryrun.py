import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count on first init.
#
# Multi-pod dry-run: for every (architecture x input-shape x mesh) cell,
# lower + compile the step function on the production mesh with
# ShapeDtypeStruct stand-ins (no allocation), print memory/cost analysis,
# and write a JSON artifact consumed by the roofline table
# (EXPERIMENTS.md section Dry-run / section Roofline).
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
#   python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]

import argparse
import json
import sys
import time
import traceback

import jax

from repro.core.config import (LM_SHAPES, OptimizerConfig, get_arch,
                               list_archs)
from repro.core.hlo.analysis import analyze_compiled
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import api
from repro.sharding import activation_rules


def dryrun_cell(arch_id: str, shape_name: str, multi_pod: bool = False,
                seq_parallel=None, verbose: bool = True,
                remat: str = "full") -> dict:
    """Lower + compile one cell; returns the roofline artifact dict.

    Baseline remat='full': recompute per layer in backward — conservative
    memory (the CPU dry-run backend also up-casts bf16 dot operands to f32,
    so memory_analysis here is an upper bound vs real TPU).
    """
    spec = get_arch(arch_id)
    cfg = spec.model
    shape = LM_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_str = mesh_lib.mesh_name(mesh)
    if seq_parallel is None:
        seq_parallel = shape.mode == "decode"

    t0 = time.perf_counter()
    params_shapes = api.param_shapes(cfg)
    inputs = api.input_specs(cfg, shape)

    with activation_rules(mesh, seq_parallel=seq_parallel):
        if shape.mode == "train":
            opt_cfg = OptimizerConfig()
            _, opt_shapes = steps_lib.train_state_shapes(cfg, opt_cfg)
            jitted, _ = steps_lib.jit_train_step(
                cfg, opt_cfg, mesh, inputs, remat=remat,
                seq_parallel=seq_parallel)
            lowered = jitted.lower(params_shapes, opt_shapes, inputs)
        elif shape.mode == "prefill":
            sh = mesh_lib.shardings_for(cfg, shape, mesh, params_shapes,
                                        None, inputs,
                                        seq_parallel=seq_parallel)
            step_fn, _ = steps_lib.step_for_shape(cfg, shape)
            jitted = jax.jit(step_fn,
                             in_shardings=(sh["params"], sh["batch"]))
            lowered = jitted.lower(params_shapes, inputs)
        else:  # decode
            sh = mesh_lib.shardings_for(cfg, shape, mesh, params_shapes,
                                        None, inputs,
                                        seq_parallel=seq_parallel)
            step_fn, _ = steps_lib.step_for_shape(cfg, shape)
            jitted = jax.jit(
                step_fn,
                in_shardings=(sh["params"], sh["state"], sh["tokens"],
                              sh["pos"]),
                out_shardings=(None, sh["state"]),
                donate_argnums=(1,))
            lowered = jitted.lower(params_shapes, inputs["state"],
                                   inputs["tokens"], inputs["pos"])
        t_lower = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    if verbose:
        print(f"[{arch_id} | {shape_name} | mesh {mesh_str}]")
        print(f"  lower {t_lower:.1f}s  compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
    report = analyze_compiled(compiled)
    report.update({
        "arch": arch_id, "shape": shape_name, "mesh": mesh_str,
        "chips": mesh.devices.size, "multi_pod": multi_pod,
        "seq_parallel": seq_parallel,
        "lower_seconds": t_lower, "compile_seconds": t_compile,
        "model_flops": api.model_flops(cfg, shape),
        "param_count": api.param_count(cfg),
        "active_param_count": api.param_count(cfg, active_only=True),
    })
    if verbose:
        ca = compiled.cost_analysis() or {}
        print(f"  cost_analysis flops={ca.get('flops', 0):.3e} "
              f"(walker: {report['flops']:.3e})")
        print(f"  per-device: flops={report['flops']:.3e} "
              f"hbm={report['hbm_bytes'] / 1e9:.2f}GB "
              f"coll={report['collective_bytes'] / 1e9:.3f}GB "
              f"peak_mem={report.get('peak_bytes', 0) / 1e9:.2f}GB")
    return report


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", type=str, default=None)
    p.add_argument("--shape", type=str, default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", type=str, default="runs/dryrun")
    args = p.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for aid in list_archs():
            spec = get_arch(aid)
            for s in spec.shapes:
                if s in spec.skip_shapes:
                    continue
                cells.append((aid, s))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for aid, s in cells:
        for mp in meshes:
            tag = f"{aid}_{s}_{'512' if mp else '256'}"
            try:
                rep = dryrun_cell(aid, s, multi_pod=mp)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rep, f, indent=1)
            except Exception as e:
                traceback.print_exc()
                failures.append((tag, str(e)))
    if failures:
        print(f"\nFAILED {len(failures)} cells:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        sys.exit(1)
    print(f"\nOK: {len(cells) * len(meshes)} cells")


if __name__ == "__main__":
    main()
