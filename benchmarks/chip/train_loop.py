"""Training loop: the program's jitted train step, run for a window.

Set-up builds one object, the step from ``steps.jit_train_step`` with its
parameters (made by ``weights.py`` from the seed) and its optimizer state,
and drives it through the first three steps on distinct batches through
the same call the window makes.  It reads what the check needs before the
next step donates it: each step's loss, the clipped first gradient from
the Adam state after step 1, and the parameters' change after step 3.  The
window then continues the same object from step 4.

After the window and after the peak memory is read, the program's state is
freed and the configuration's reference (``harness.reference_for``)
repeats the three steps from the same weights, in float32 at the highest
precision.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import correctness
import harness
import traffic_gen
import weights

CHECKED_STEPS = 3


def _program(cell, cfg):
    from repro.core.config import OptimizerConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps as steps_lib
    from repro.models import api
    from repro.optim import adamw

    t = cell.traffic
    opt_cfg = OptimizerConfig(**t["optimizer"])
    mesh = mesh_lib.make_elastic_mesh(1, 1)
    specs = {"tokens": jax.ShapeDtypeStruct((t["batch"], t["seq_len"]),
                                            jnp.int32)}
    step, sh = steps_lib.jit_train_step(cfg, opt_cfg, mesh, specs,
                                        remat=t["remat"])
    shapes = api.param_shapes(cfg)
    init_opt = jax.jit(lambda p: adamw.init_opt_state(p, opt_cfg),
                       out_shardings=sh["opt_state"])
    return mesh, step, sh, shapes, init_opt, opt_cfg


def setup(cell, seed: int, break_step=None) -> Dict:
    """The one object the window drives, after the three checked steps,
    and the program's readings.  ``break_step`` wraps the step with a
    planted fault (tests and calibration only)."""
    t = cell.traffic
    cfg = harness.program_config(cell.config, t)
    B, V = t["batch"], cfg.vocab_size
    nb = max(t["data"]["distinct_batches"], CHECKED_STEPS)
    host_batches = [traffic_gen.train_batch(t, seed, i, B, V)
                    for i in range(nb)]
    mesh, step, sh, shapes, init_opt, opt_cfg = _program(cell, cfg)
    if break_step is not None:
        step = break_step(step, cfg, opt_cfg, sh)
    from repro.sharding import activation_rules

    with activation_rules(mesh):
        params = weights.make_params(shapes, seed, sh["params"])
        opt_state = init_opt(params)
        batches = [{"tokens": jax.device_put(b, sh["batch"]["tokens"])}
                   for b in host_batches]
        losses = []
        for i in range(CHECKED_STEPS):
            params, opt_state, m = step(params, opt_state, batches[i])
            losses.append(float(m["loss"]))
            if i == 0:
                g = correctness.slice_norms(opt_state["m"])
                grad = {k: v / (1.0 - opt_cfg.b1) for k, v in g.items()}
        change = correctness.change_norms(params, shapes, seed)
        jax.block_until_ready((params, opt_state))
    return {"mesh": mesh, "step": step, "params": params,
            "opt_state": opt_state, "batches": batches,
            "host_batches": host_batches, "shapes": shapes,
            "prog": {"losses": losses, "grad": correctness.flat(grad),
                     "change": correctness.flat(change)}}


def run(cell, seed: int, seconds: float, trace_dir, clock, t_start,
        break_step=None) -> Dict:
    from repro.sharding import activation_rules

    t = cell.traffic
    B, S = t["batch"], t["seq_len"]
    o = setup(cell, seed, break_step)
    step, params, opt_state, batches = (o["step"], o["params"],
                                        o["opt_state"], o["batches"])
    nb = len(batches)
    rec: Dict = {"kind": "train", "batch": B, "seq_len": S,
                 "prog": o["prog"], "setup_s": time.perf_counter() - t_start,
                 "setup_compile": clock.lap()}
    window_s = min(seconds, t["trace_seconds"]) if trace_dir else seconds
    prof = jax.profiler.trace(trace_dir) if trace_dir else \
        contextlib.nullcontext()
    done, bad, i = 0, 0, CHECKED_STEPS
    with activation_rules(o["mesh"]), prof:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train_step"):
            params, opt_state, m = step(params, opt_state, batches[i % nb])
        i += 1
        while True:
            with jax.profiler.TraceAnnotation("train_step"):
                prev = m
                more = time.perf_counter() - t0 < window_s
                if more:
                    params, opt_state, m = step(params, opt_state,
                                                batches[i % nb])
                    i += 1
                loss = float(prev["loss"])
            done += 1
            bad += not np.isfinite(loss)
            if not more:
                break
        t_end = time.perf_counter()
    rec["window_compile"] = clock.lap()
    rec.update(steps=done, failed=bad, window_s=t_end - t0,
               tokens=done * B * S)
    rec["train_tok_s"] = rec["tokens"] / rec["window_s"]
    rec["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])
    host_batches, shapes = o["host_batches"], o["shapes"]
    del o, params, opt_state, m, prev, batches
    gc.collect()
    t_ref = time.perf_counter()
    rec["ref"] = reference_readings(cell, shapes, seed, host_batches)
    rec["reference_s"] = time.perf_counter() - t_ref
    return rec


def readings(rec) -> Dict:
    return correctness.train_readings(rec["prog"], rec["ref"])


def reference_readings(cell, shapes, seed: int, host_batches,
                       mm=None) -> Dict:
    """The reference's three steps from the weights ``seed`` makes, in
    float32 (``mm`` = the reference's ``fp8_mm`` gives the control)."""
    t = cell.traffic
    reference = harness.reference_for(cell.config, cell.here)
    mm = mm or reference.highest_mm
    rc = reference.RefConfig.from_file(cell.config)
    f32_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shapes)
    with jax.default_matmul_precision("highest"):
        W = weights.make_params(f32_shapes, seed)
        batches = [jnp.asarray(b) for b in host_batches[:CHECKED_STEPS]]
        losses, g1, W = reference.train(mm, rc, W, batches, t["optimizer"],
                                        CHECKED_STEPS)
        grad = correctness.slice_norms(g1)
        del g1
        change = correctness.change_norms(W, f32_shapes, seed)
        del W
    return {"losses": losses, "grad": correctness.flat(grad),
            "change": correctness.flat(change)}
