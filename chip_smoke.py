#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU chip (not a benchmark).

    python chip_smoke.py                # one chip: serve qwen1.5-0.5b
    python chip_smoke.py --four-chips   # four chips: one sharded train step

One chip: ``repro.launch.serve.main`` serves qwen1.5-0.5b at its full
registered width and dtype (bf16, 24 layers) with random weights from a
fixed seed: request 0 alone, then 8 requests of 64 prompt tokens and 32
new tokens over 4 slots.  Every request must finish with 32 tokens in
[0, vocab) sampled from finite logits, and request 0 must decode the same
tokens in the batch as alone.

Four chips: one train step of the same config (batch 8, seq 512) on a
(data=1, model=4) mesh, with params and optimizer state placed through
``repro.launch.mesh.shardings_for``, against the same step on device 0
alone.  The losses must agree and the state must be split across the four
devices.

Everything runs in this one process, which holds the chip; nothing is
forked.  The last line of stdout is one JSON object naming the device; any
failed check, or a first device that is not a TPU, exits non-zero first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.config import OptimizerConfig, get_arch  # noqa: E402
from repro.core.hw import SystemDescription, tpu_v5e_chip  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline  # noqa: E402
from repro.launch import common, serve  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.serve_sim.cost import ServingCostModelBuilder  # noqa: E402
from repro.sharding import activation_rules  # noqa: E402

ARCH = "qwen1.5-0.5b"
SLOTS, PROMPT, NEW, MAX_LEN, REQUESTS = 4, 64, 32, 2048, 8
SERVE_ARGS = ["--arch", ARCH, "--slots", str(SLOTS), "--prompt-len",
              str(PROMPT), "--max-new", str(NEW), "--max-len", str(MAX_LEN)]
TRAIN_BATCH, TRAIN_SEQ, TRAIN_REMAT = 8, 512, "dots"
LOSS_RTOL = 1e-2          # bf16 matmuls summed in another order per mesh
TAG = "[one-chip smoke, not a benchmark]"


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    loads included), read from its monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def predicted_decode_step(cfg) -> float:
    """The virtual model's decode-step time for this run's batch (analytic
    backend, serial), in seconds."""
    one_chip = SystemDescription(name="tpu_v5e_1chip", chip=tpu_v5e_chip(),
                                 torus=())
    model = ServingCostModelBuilder(cfg).model_for(one_chip)
    return model.decode_step_time(SLOTS, SLOTS * (PROMPT + NEW // 2))


def serve_phase(clock: CompileClock) -> None:
    cfg = common.run_config(get_arch(ARCH), smoke=False)
    print(f"{TAG} {ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {cfg.param_dtype}")
    print(f"[prediction, virtual model, not measured] decode step with "
          f"{SLOTS} active slots: {predicted_decode_step(cfg) * 1e3:.3f} ms")

    print(f"{TAG} request 0 alone:")
    solo = serve.main(SERVE_ARGS + ["--requests", "1"])
    print(f"{TAG} compile {clock.lap():.1f} s (first call of each program)")
    print(f"{TAG} {REQUESTS} requests over {SLOTS} slots:")
    batch = serve.main(SERVE_ARGS + ["--requests", str(REQUESTS)])
    print(f"{TAG} compile {clock.lap():.1f} s")

    check(len(batch) == REQUESTS and all(r.done for r in batch),
          "not every request finished")
    for r in batch + solo:
        toks = np.asarray(r.out)
        check(len(toks) == NEW, f"request {r.rid}: {len(toks)} tokens")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
              f"request {r.rid}: token outside [0, {cfg.vocab_size})")
        check(r.finite, f"request {r.rid}: non-finite logits")
    check(np.array_equal(solo[0].prompt, batch[0].prompt),
          "request 0 has another prompt alone")
    check(solo[0].out == batch[0].out,
          f"request 0 decodes differently in the batch: "
          f"{batch[0].out} vs alone {solo[0].out}")
    print(f"{TAG} all {REQUESTS} requests: {NEW} tokens in range, finite "
          f"logits; request 0 matches its solo decode")


def four_chip_phase(clock: CompileClock) -> None:
    check(jax.device_count() >= 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    cfg = common.run_config(get_arch(ARCH), smoke=False)
    opt_cfg = OptimizerConfig()
    batch = SyntheticTokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
        global_batch=TRAIN_BATCH)).batch_at(0)
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}
    tag = "[four-chip smoke, not a benchmark]"

    losses = {}
    # the sharded mesh first, so device 0 holds nothing else when its
    # share of the state is read
    for name, n in (("model=4", 4), ("device 0", 1)):
        mesh = mesh_lib.make_elastic_mesh(n, model_parallel=n)
        with activation_rules(mesh):
            jitted, sh = steps_lib.jit_train_step(
                cfg, opt_cfg, mesh, specs, remat=TRAIN_REMAT)
            params, opt_state = steps_lib.init_train_state(
                jax.random.key(0), cfg, opt_cfg, sh)
            jax.block_until_ready((params, opt_state))
            state_bytes = sum(x.nbytes for x in
                              jax.tree.leaves((params, opt_state)))
            in_use = [d.memory_stats()["bytes_in_use"]
                      for d in mesh.devices.flat]
            t0 = time.perf_counter()
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            gnorm = float(metrics["grad_norm"])
        print(f"{tag} mesh {mesh_lib.mesh_name(mesh)} ({name}): "
              f"loss {loss!r} grad_norm {gnorm!r}; first step {dt:.1f} s "
              f"(compile {clock.lap():.1f} s); state "
              f"{state_bytes / 2**30:.3f} GiB, bytes_in_use per device "
              f"{[round(b / 2**30, 3) for b in in_use]} GiB")
        check(np.isfinite(loss), f"{name}: non-finite loss")
        if n > 1:
            check(all(state_bytes / (2 * n) < b < state_bytes / 2
                      for b in in_use),
                  f"state not split across {n} devices: {in_use} bytes "
                  f"in use for {state_bytes} bytes of state")
        losses[name] = loss
        del params, opt_state, metrics
    check(abs(losses["model=4"] - losses["device 0"])
          <= LOSS_RTOL * abs(losses["device 0"]),
          f"losses disagree: {losses}")
    print(f"{tag} losses agree within {LOSS_RTOL}; state split over 4 "
          f"devices")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded train step on four chips")
    args = p.parse_args(argv)

    common.enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"{TAG} device {dev.device_kind}, {jax.device_count()} device(s)")
    clock = CompileClock()
    try:
        if args.four_chips:
            four_chip_phase(clock)
        else:
            serve_phase(clock)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
