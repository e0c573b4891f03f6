"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --smoke --steps 50 --batch 8 --seq 128

Wires together: config registry -> mesh + sharding -> synthetic data
pipeline (prefetching) -> jitted train step (donated state) -> checkpoint
manager (async, atomic, auto-resume) -> supervisor heartbeats.  ``--smoke``
selects the reduced config in f32 (CPU-runnable); without it the full
config trains in its registered dtype.  On several devices the mesh is
(data, model) and params and optimizer state are created in the shardings
of ``repro.launch.mesh.shardings_for``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.config import OptimizerConfig, get_arch
from repro.data.pipeline import DataConfig, PrefetchIterator, \
    SyntheticTokenPipeline
from repro.launch import common
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import api
from repro.runtime.supervisor import Supervisor
from repro.sharding import activation_rules


def model_batch(cfg, host_batch, batch: int, seq: int):
    """The step's input dict (host arrays) for one pipeline batch."""
    out = dict(host_batch)
    if cfg.family == "vlm":
        npre = min(cfg.frontend.num_prefix, seq // 2)
        out["prefix_embeds"] = np.zeros((batch, npre, cfg.d_model),
                                        np.float32)
    elif cfg.family in ("audio", "encdec"):
        out = {"frames": np.zeros((batch, seq // 2, cfg.d_model), np.float32),
               "tokens": out["tokens"][:, :seq // 2]}
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--remat", default="none")
    p.add_argument("--grad-compression", default="none",
                   choices=["none", "int8_ef"])
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--dtype", default=None,
                   help="param/compute dtype (default: the config's own; "
                        "float32 with --smoke)")
    args = p.parse_args(argv)

    common.enable_compile_cache()
    cfg = common.run_config(get_arch(args.arch), args.smoke, args.dtype)
    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=args.warmup,
                              total_steps=args.steps,
                              grad_compression=args.grad_compression)

    n_dev = jax.device_count()
    mesh = mesh_lib.make_elastic_mesh(n_dev, model_parallel=min(n_dev, 16) if n_dev > 1 else 1)
    print(f"devices={n_dev} mesh={mesh_lib.mesh_name(mesh)} "
          f"arch={cfg.name} params≈{api.param_count(cfg):,}")

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    pipeline = SyntheticTokenPipeline(data_cfg)
    batch_specs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype)
        for k, v in model_batch(
            cfg, {"tokens": np.zeros((args.batch, args.seq), np.int32)},
            args.batch, args.seq).items()}

    with activation_rules(mesh):
        jitted, sh = steps_lib.jit_train_step(cfg, opt_cfg, mesh, batch_specs,
                                              remat=args.remat)
        state_sh = {"params": sh["params"], "opt_state": sh["opt_state"]}
        ckpt = CheckpointManager(args.ckpt_dir)
        start_step = 0
        if args.resume and ckpt.latest_step() is not None:
            start_step, state = ckpt.restore(shardings=state_sh)
            params, opt_state = state["params"], state["opt_state"]
            print(f"resumed from step {start_step}")
        else:
            params, opt_state = steps_lib.init_train_state(
                jax.random.key(0), cfg, opt_cfg, sh)

        sup = Supervisor(num_workers=1)
        prefetch = PrefetchIterator(pipeline, start_step=start_step)
        losses = []
        t_start = time.perf_counter()
        try:
            for _ in range(start_step, args.steps):
                step_i, host_batch = next(prefetch)
                batch = model_batch(cfg, host_batch, args.batch, args.seq)
                t0 = time.perf_counter()
                params, opt_state, metrics = jitted(params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                sup.heartbeat(0, step_i, dt)
                losses.append(loss)
                if (step_i + 1) % args.log_every == 0:
                    moe = (f"held {float(metrics['moe_held_assignments']):.0f}"
                           f"  max load "
                           f"{float(metrics['moe_max_load']):5.2f}  "
                           if "moe_held_assignments" in metrics else "")
                    print(f"step {step_i + 1:5d}  loss {loss:8.4f}  "
                          f"gnorm {float(metrics['grad_norm']):7.3f}  {moe}"
                          f"lr {float(metrics['lr']):.2e}  {dt * 1e3:7.1f} ms")
                if (step_i + 1) % args.ckpt_every == 0:
                    ckpt.save(step_i + 1,
                              {"params": params, "opt_state": opt_state})
        finally:
            prefetch.close()
            ckpt.wait()
        wall = time.perf_counter() - t_start
        print(f"done: {args.steps - start_step} steps in {wall:.1f}s; "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        ckpt.save(args.steps, {"params": params, "opt_state": opt_state})
        ckpt.wait()
        return losses


if __name__ == "__main__":
    main()
