#!/usr/bin/env python3
"""Record the small scoped device trace that the tests of ``scopes.py``
read.

    python3 benchmarks/chip/tests/record_scoped_trace.py [OUT_DIR]

Needs the chip.  Traces three calls of the program's jitted train step
(``steps.jit_train_step``) for a two-layer, smoke-width qwen1.5 (f32
parameters, bf16 compute, one sequence of 2048 tokens, so attention takes
the chunked path of the cell), each inside a ``train_step`` span, and
writes the raw ``.xplane.pb`` to OUT_DIR (default ``testdata/``) as
``scoped.xplane.pb``, without the ``/host:metadata`` plane (the compiled
programs, 1 MB that no reader needs).  Prints the file's size and the
per-scope times.
"""
from __future__ import annotations

import dataclasses
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import scopes  # noqa: E402
import trace_reduce  # noqa: E402

SEQ = 2048
DROP = "/host:metadata"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def strip_plane(src: Path, dst: Path, drop: str = DROP) -> None:
    """Copy an ``.xplane.pb`` leaving out the plane named ``drop``: the
    file is an ``XSpace`` whose fields are all length-delimited."""
    out = bytearray()
    for num, wt, v in scopes._fields(memoryview(src.read_bytes())):
        if wt != 2:
            raise ValueError(f"{src}: field {num} of wire type {wt} in an "
                             f"XSpace")
        if num == 1 and any(pn == 2 and bytes(pv).decode() == drop
                            for pn, _, pv in scopes._fields(v)):
            continue
        out += _varint(num << 3 | 2) + _varint(len(v)) + bytes(v)
    dst.write_bytes(bytes(out))


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 1
    from repro.core.config import OptimizerConfig, get_arch
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps as steps_lib
    from repro.sharding import activation_rules

    cfg = dataclasses.replace(get_arch("qwen1.5-0.5b").smoke,
                              param_dtype="float32",
                              compute_dtype="bfloat16")
    opt = OptimizerConfig()
    mesh = mesh_lib.make_elastic_mesh(1, 1)
    specs = {"tokens": jax.ShapeDtypeStruct((1, SEQ), jnp.int32)}
    step, sh = steps_lib.jit_train_step(cfg, opt, mesh, specs)
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE.parent / "testdata"
    out.mkdir(parents=True, exist_ok=True)
    tmp = HERE.parents[2] / ".bench_trace" / "record_scoped_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    with activation_rules(mesh):
        params, opt_state = steps_lib.init_train_state(jax.random.key(0),
                                                       cfg, opt, sh)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (1, SEQ),
                                              0, cfg.vocab_size)}
        params, opt_state, m = step(params, opt_state, batch)
        jax.block_until_ready(m)
        with jax.profiler.trace(str(tmp)):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("train_step"):
                    params, opt_state, m = step(params, opt_state, batch)
                    jax.block_until_ready(m)
    path = trace_reduce.find_xplane(str(tmp))
    dst = out / "scoped.xplane.pb"
    strip_plane(Path(path), dst)
    print(f"{dst}: {dst.stat().st_size} bytes")
    times = scopes.scope_times(str(dst))
    if times is None:
        print("record_scoped_trace: no scoped op in the trace",
              file=sys.stderr)
        return 1
    for k, v in sorted(times.items()):
        print(f"{k}: {v['s'] * 1e6:.1f} us, {v['flops']:.4g} FLOP a call")
    return 0


if __name__ == "__main__":
    sys.exit(main())
