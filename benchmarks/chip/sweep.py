#!/usr/bin/env python3
"""Knee sweep: one serving mix at a list of Poisson rates, in one process.

    python3 benchmarks/chip/sweep.py --workload qwen1.5-0.5b.decode_chat \
        --rates 0.5 1 1.5 2 --seconds 40 --seed 5

For each rate the open loop runs ``--seconds`` of load (arrivals then stop
being counted, as in a run), and the queue-growth test is printed: the
mean length of the admission queue over the last third of the window
against the first third.  A rate holds when the last third's mean is at
most the first third's plus one request; the knee is the highest rate
that holds.  TTFT and ITL percentiles and the output rate are printed
beside it.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import numpy as np  # noqa: E402

import bench  # noqa: E402
import harness  # noqa: E402
import serve_loop as sd  # noqa: E402
import traffic_gen  # noqa: E402


def queue_growth(qlog, window_s: float):
    """(mean queue over the first third, over the last third, holds)."""
    third = window_s / 3
    first = [q for t, q in qlog if t < third]
    last = [q for t, q in qlog if 2 * third <= t < window_s]
    a = float(np.mean(first)) if first else 0.0
    b = float(np.mean(last)) if last else 0.0
    return a, b, b <= a + 1.0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    try:
        cell = harness.find_cell(args.workload)
    except harness.BenchError:
        cell = harness.cell_from_files(args.workload)
    bench.setup_jax()
    harness.check_device(cell.chips, harness.load_json(HERE / "peaks.json"))
    rows = []
    for rate in args.rates:
        cfg, server, _ = sd.build(cell, args.seed)   # empty slots
        mix = json.loads(json.dumps(cell.traffic))
        mix["arrivals"]["rate"] = rate
        plan = traffic_gen.serve_schedule(mix, args.seed,
                                          2 * args.seconds + 1.0,
                                          cfg.vocab_size)
        reqs, toks, work, t0, late, qlog = sd.serve(server, plan,
                                                    args.seconds, False)
        m = sd.metrics(reqs, toks, t0, args.seconds, time.perf_counter())
        del server, reqs
        a, b, holds = queue_growth(qlog, args.seconds)
        row = {"rate": rate, "queue_first_third": a, "queue_last_third": b,
               "holds": holds, "ttft_p95_ms": m["ttft_p95_ms"],
               "itl_p95_ms": m["itl_p95_ms"], "out_tok_s": m["out_tok_s"],
               "requests": m["attempted"], "failed": m["failed"],
               "generator_late_s": late}
        rows.append(row)
        print(json.dumps(row), flush=True)
    held = [r["rate"] for r in rows if r["holds"]]
    print(json.dumps({"knee": max(held) if held else None,
                      "rule": "mean queue over the last third <= first "
                              "third + 1"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
