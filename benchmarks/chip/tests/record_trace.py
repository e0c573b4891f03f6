#!/usr/bin/env python3
"""Record the small device trace that ``test_benchmark_chip.py`` reduces.

    python3 benchmarks/chip/tests/record_trace.py [OUT_DIR]

Needs the chip.  Traces three calls of a small jitted step, each inside a
``train_step`` span with a host sleep between them (so that the trace has
idle gaps), writes the raw ``.xplane.pb`` and its events as read by
``trace_reduce.read_xplane`` to OUT_DIR (default ``testdata/``), and
prints every plane and line name with its event count.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_reduce  # noqa: E402


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    step = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE.parent / "testdata"
    out.mkdir(parents=True, exist_ok=True)
    tmp = Path(HERE.parents[2] / ".bench_trace" / "record_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    with jax.profiler.trace(str(tmp)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train_step"):
                step(x).block_until_ready()
                time.sleep(0.002)
    path = trace_reduce.find_xplane(str(tmp))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            print(plane.name, "|", line.name, "|", len(list(line.events)))
    shutil.copy(path, out / "small.xplane.pb")
    (out / "small_trace.json").write_text(
        trace_reduce.read_xplane(path).to_json())
    print(trace_reduce.reduce(trace_reduce.read_xplane(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
