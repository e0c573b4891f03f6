#!/usr/bin/env python3
"""One traced window of a serving cell, read by the program's own names.

    python3 benchmarks/chip/trace_serve.py --workload qwen1.5-0.5b.decode_chat \
        --rate 0.2 --seconds 20 --seed 4300000001

Builds the cell from its files (``harness.cell_from_files``, as
``calibrate.py`` does), loads ``BatchedServer`` with the seed's weights and
a ``repro.obs.Probe``, warms it up, and runs ``serve_loop.serve``'s open
loop under ``jax.profiler.trace`` for ``--seconds`` (and until every
request due in the window has its first token).  Then it prints:

* the decode step's device time and executed FLOPs by named scope, per
  call of ``jit_serve_step`` (``scopes.scope_times``), with each scope's
  costliest ops;
* for each program span (``serve.admit`` > ``serve.prefill``,
  ``serve.step`` > ``serve.decode``, ``serve.sample``): its host seconds
  and the device seconds busy inside it;
* the devices' idle seconds, each gap put down to the innermost program
  span open at its midpoint (``scopes.idle_by_span``);
* how the host clock sits against the device's (``scopes.clock_lag``);
* the server's counters in the window and the slot occupancy
  ``slot_steps / (decode_steps x slots)``.

The last line of stdout is the same as one JSON object, which is also
written to ``chiprun_out/trace_serve/<workload>.<seed>.json``.  The
benchmark's own runs never run this.  It needs the chip.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import bench  # noqa: E402
import harness  # noqa: E402


def build(cell, seed: int, probe):
    """``serve_loop.build`` with a probe on the server."""
    import numpy as np
    from repro.launch.serve import BatchedServer, Request
    from repro.models import api

    import weights

    cfg = harness.program_config(cell.config, cell.traffic)
    server = BatchedServer(cfg, cell.config["serve_slots"],
                           cell.traffic["max_len"], probe=probe)
    server.load(weights.make_params(api.param_shapes(cfg), seed))
    server.admit(Request(-1, np.zeros(2, np.int32), 1))
    server.step()
    return cfg, server


def reduce(path: str, slots: int, counters) -> dict:
    import scopes
    import trace_reduce

    tr = trace_reduce.read_xplane(path)
    meta = scopes.read_metadata(path)
    spans = scopes.program_spans(path)
    out = {"window": scopes.device_window(tr, spans),
           "scopes": scopes.times_by_scope(tr, meta, scopes.SERVE_MODULE),
           "spans": scopes.busy_in_spans(tr, spans),
           "idle_by_span": scopes.idle_by_span(tr, spans),
           "clock": scopes.clock_lag(tr, spans, "serve.decode",
                                     scopes.SERVE_MODULE),
           "top_ops": scopes.top_ops(tr, meta, scopes.SERVE_MODULE),
           "counters": counters}
    steps = counters.get("serve/decode_steps", 0)
    out["slot_occupancy"] = counters.get("serve/slot_steps", 0) / \
        (steps * slots) if steps else None
    return out


def report(r: dict) -> list:
    w = r["window"]
    lines = [f"window {w['window_s']:.3f} s, device busy {w['busy_s']:.3f} s "
             f"({100 * w['busy_s'] / w['window_s']:.2f}%)"]
    if r["scopes"]:
        total = sum(v["s"] for v in r["scopes"].values())
        lines.append(f"jit_serve_step: {1e3 * total:.3f} ms of device time "
                     f"a call")
        for k, v in sorted(r["scopes"].items(), key=lambda kv: -kv[1]["s"]):
            lines.append(f"  {k}: {1e3 * v['s']:.3f} ms "
                         f"({100 * v['s'] / total:.1f}%), "
                         f"{v['flops']:.4g} FLOP")
            for op, tf_op, d in r["top_ops"].get(k, []):
                lines.append(f"    {1e3 * d:.3f} ms {op} [{tf_op}]")
    for k, v in sorted(r["spans"].items()):
        lines.append(f"span {k}: {v['count']} x, {v['span_s']:.3f} s on the "
                     f"host, device busy {v['busy_s']:.3f} s inside "
                     f"({100 * v['busy_s'] / max(v['span_s'], 1e-12):.1f}%)")
    for k, v in sorted(r["idle_by_span"].items(), key=lambda kv: -kv[1]):
        lines.append(f"idle in {k}: {v:.3f} s")
    lines.append(f"clock: {r['clock']}")
    lines.append(f"counters: {r['counters']}, slot occupancy "
                 f"{r['slot_occupancy']}")
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rate", type=float, default=None,
                   help="Poisson rate in place of the mix's")
    args = p.parse_args(argv)
    cell = harness.cell_from_files(args.workload)
    jax = bench.setup_jax()
    harness.check_device(cell.chips, harness.load_json(HERE / "peaks.json"))
    if args.rate:
        cell.traffic["arrivals"]["rate"] = args.rate
    from repro.obs import Probe

    import serve_loop
    import trace_reduce
    import traffic_gen

    probe = Probe("serve")
    cfg, server = build(cell, args.seed, probe)
    plan = traffic_gen.serve_schedule(cell.traffic, args.seed,
                                      2 * args.seconds + 1.0, cfg.vocab_size)
    jax.block_until_ready(server.state)
    before = dict(probe.to_metrics()["counters"])
    trace_dir = ROOT / ".bench_trace" / f"trace_serve.{args.workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(str(trace_dir)):
        reqs, toks, _, t0, late, _ = serve_loop.serve(server, plan,
                                                       args.seconds, False)
    t_stop = time.perf_counter()
    after = probe.to_metrics()["counters"]
    counters = {k: v - before.get(k, 0.0) for k, v in after.items()}
    m = serve_loop.metrics(reqs, toks, t0, args.seconds, t_stop)
    t_red = time.perf_counter()
    r = reduce(trace_reduce.find_xplane(str(trace_dir)), server.slots,
               counters)
    r["reduce_s"] = time.perf_counter() - t_red
    r.update(workload=args.workload, seed=args.seed, rate=args.rate,
             generator_late_s=late, requests=m["attempted"],
             failed=m["failed"], ttft_p95_ms=m["ttft_p95_ms"],
             itl_p95_ms=m["itl_p95_ms"], out_tok_s=m["out_tok_s"],
             device={"kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices())})
    for line in report(r):
        print(line)
    text = json.dumps(r, default=float)
    out = ROOT / "chiprun_out" / "trace_serve"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.{args.seed}.json").write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
