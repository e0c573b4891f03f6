#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/bench.py --workload qwen1.5-0.5b.train_4k \
        --seed 7 --seconds 20 --trace 0

The cell's configuration, traffic mix, limits, per-layer readers,
reference and counts are found by name (see ``harness.py``).  Everything
runs in this one process, which holds the chip; nothing is forked.
Set-up (JAX start, weights made
on the device from the seed, compilation or a load from the persistent
cache, warm-up) counts as ``setup_s``; then the window runs for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared with its limit.
The same numbers close stderr.  With no TPU, a device kind missing from
``peaks.json`` or fewer chips than the cell asks for, it prints no result
and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402

TRACE_ROOT = HERE.parents[1] / ".bench_trace"


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_jax():
    """Persistent compilation cache where the program keeps it (inside the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), for every program."""
    import jax
    from repro.launch import common

    common.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Context:
    """What a per-layer reader sees: the cell, the run's record, the reduced
    trace, the chip's peaks, and ``counts``, the configuration's operation
    and byte counts (``harness.counts_for``)."""

    def __init__(self, cell, rec, red, peak):
        self.cell, self.rec, self.trace, self.peak = cell, rec, red, peak
        self.config, self.traffic = cell.config, cell.traffic
        self.counts = harness.counts_for(cell.config, cell.here)


def run_cell(args, cell=None, devices=None, break_step=None):
    """Returns (result dict, stderr lines).  ``cell``, ``devices`` and
    ``break_step`` are for the tests: a cell of their own, the CPU in
    place of the chip, and a planted fault in the timed path."""
    import correctness
    import trace_reduce

    cell = cell or harness.find_cell(args.workload)
    peaks = harness.load_json(HERE / "peaks.json")
    setup_jax()
    devs = devices or harness.check_device(cell.chips, peaks)
    kind = devs[0].device_kind
    clock = harness.CompileClock()
    trace_dir = None
    if args.trace:
        trace_dir = str(TRACE_ROOT / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    if cell.traffic["kind"] == "train":
        import train_loop as loop
    else:
        import serve_loop as loop
    kw = {"break_step": break_step} if break_step else {}
    rec = loop.run(cell, args.seed, args.seconds, trace_dir, clock, T_START,
                   **kw)
    readings = loop.readings(rec)
    correct, rows = correctness.judge(readings, cell.limits["limits"])
    lines = [f"window: {rec['window_compile'][1]} compilations, "
             f"{rec['window_compile'][0]:.3f} s compiling inside the window",
             f"set-up compile: {rec['setup_compile'][1]} programs, "
             f"{rec['setup_compile'][0]:.3f} s",
             f"reference after the window: {rec['reference_s']:.1f} s"]
    for k, v in readings.items():
        if k.startswith("_"):
            lines.append(f"check detail {k[1:]}: {v}")
    if rec["window_compile"][1]:
        correct = False
        lines.append("not correct: a program compiled inside the window")
    result = {"correct": bool(correct), "attempted": rec["steps"],
              "failed": rec["failed"]}
    metrics = {}
    if args.trace:
        red = trace_reduce.reduce(trace_reduce.read_xplane(
            trace_reduce.find_xplane(trace_dir)))
        ctx = Context(cell, rec, red, peaks.get(kind, {}))
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"], cell.here)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(rec[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": devs[0].platform, "kind": kind,
                        "count": len(devs),
                        "memory_peak_bytes": int(rec["memory_peak_bytes"])}
    if args.trace:
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    lines += [f"check {k}: {v!r} (limit {lim!r})" for k, v, lim in rows]
    return result, lines


def main(argv=None) -> int:
    args = _args(argv)
    try:
        result, lines = run_cell(args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))           # "checks" is the last key
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
