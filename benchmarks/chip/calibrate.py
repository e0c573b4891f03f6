#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload qwen1.5-0.5b.train_4k \
        --seeds 11 12 13 --control 11 12 --faults 11 12 \
        --out calibration.json

For each seed the program's readings (``correctness.py``) against the
float32 reference, at the cell's own sizes.  For ``--control`` seeds the
control's: the configuration's reference (``harness.reference_for``) with
every matrix product rounded to float8 (its ``fp8_mm``) in the program's
place.  For ``--faults`` seeds the
program with a planted fault in place of the timed path: for training,
half of the batch left out of the loss (the mean taken over the rest); a
step that returns its state unchanged reads 1 by construction and needs no
run.  For serving (``--seconds`` of the cell's load per seed, then ``--drain``
seconds of decoding so that admitted requests finish): the served tokens'
gaps, the same tokens' gaps over the sequence the server's loop fed its
model, and the control's gaps at the same positions.

The benchmark's own runs never run this.  It needs the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402
import correctness  # noqa: E402
import harness  # noqa: E402


def half_batch(step, cfg, opt_cfg, sh):
    """The train step with the second half of the batch's tokens left out
    of the loss, whose mean is taken over the rest."""
    import jax
    import jax.numpy as jnp
    from repro.launch import steps as steps_lib

    inner = steps_lib.make_train_step(cfg, opt_cfg, remat="dots")

    def f(p, o, b):
        tok = b["tokens"]
        keep = jnp.arange(tok.size).reshape(tok.shape) < tok.size // 2
        return inner(p, o, {"tokens": tok, "loss_mask": keep})
    return jax.jit(f, donate_argnums=(0, 1))


def calibrate_train(cell, args):
    import train_loop as td

    out = {"program": {}, "control": {}, "half_batch": {}, "raw": {}}
    reference = harness.reference_for(cell.config, cell.here)
    for s in args.seeds:
        t = time.perf_counter()
        o = td.setup(cell, s)
        prog, host, shapes = o["prog"], o["host_batches"], o["shapes"]
        del o
        gc.collect()
        ref = td.reference_readings(cell, shapes, s, host)
        out["program"][s] = correctness.train_readings(prog, ref)
        out["raw"][s] = {"prog_losses": prog["losses"],
                         "ref_losses": ref["losses"]}
        if s in args.control:
            ctrl = td.reference_readings(cell, shapes, s, host,
                                         mm=reference.fp8_mm)
            out["control"][s] = correctness.train_readings(ctrl, ref)
        if s in args.faults:
            o = td.setup(cell, s, break_step=half_batch)
            fault = o["prog"]
            del o
            gc.collect()
            out["half_batch"][s] = correctness.train_readings(fault, ref)
        print(f"seed {s}: {out['program'][s]} control "
              f"{out['control'].get(s)} half_batch "
              f"{out['half_batch'].get(s)} ({time.perf_counter() - t:.1f} s)",
              flush=True)
    return out


def calibrate_serve(cell, args):
    import jax
    import serve_loop as sd
    import traffic_gen

    out = {"program": {}, "control": {}}
    reference = harness.reference_for(cell.config, cell.here)
    for s in args.seeds:
        t = time.perf_counter()
        cfg, server, shapes = sd.build(cell, s)
        plan = traffic_gen.serve_schedule(cell.traffic, s,
                                          2 * args.seconds + 1.0,
                                          cfg.vocab_size)
        reqs, toks, work, t0, late, _ = sd.serve(server, plan, args.seconds,
                                              False)
        m = sd.metrics(reqs, toks, t0, args.seconds, time.perf_counter())
        t_lim = time.perf_counter() + args.drain
        while any(server.slot_req) and time.perf_counter() < t_lim:
            server.step()
        done = [r for r in reqs if r.done]
        served = [(r.prompt, list(r.out)) for r in sd.pick_sample(done, s)]
        del server, reqs
        gc.collect()
        mm = reference.fp8_mm if s in args.control else \
            reference.highest_mm
        g = sd.reference_gaps(cell, shapes, s, served,
                              cell.traffic["max_len"], mm=mm)
        out["program"][s] = {"gap": g["gap"], "gap_fed": g["gap_fed"],
                             "tokens": sum(len(x) for _, x in served),
                             "ttft_p95_ms": m["ttft_p95_ms"],
                             "itl_p95_ms": m["itl_p95_ms"],
                             "out_tok_s": m["out_tok_s"]}
        if s in args.control:
            out["control"][s] = {"gap": g["control"]}
        print(f"seed {s}: {out['program'][s]} control "
              f"{out['control'].get(s)} ({time.perf_counter() - t:.1f} s)",
              flush=True)
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", type=int, nargs="*", default=[])
    p.add_argument("--faults", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--drain", type=float, default=0.0,
                   help="serving: seconds of decode after the window, so "
                        "that the admitted requests finish")
    p.add_argument("--rate", type=float, default=None,
                   help="serving: Poisson rate in place of the mix's")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    try:
        cell = harness.find_cell(args.workload)
    except harness.BenchError:
        cell = harness.cell_from_files(args.workload)
    bench.setup_jax()
    harness.check_device(cell.chips, harness.load_json(HERE / "peaks.json"))
    if args.rate:
        cell.traffic["arrivals"]["rate"] = args.rate
    fn = calibrate_train if cell.traffic["kind"] == "train" else \
        calibrate_serve
    out = fn(cell, args)
    text = json.dumps(out, indent=1, default=float)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
