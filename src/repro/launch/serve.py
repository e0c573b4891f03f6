"""Batched serving driver: continuous-batching loop over prefill + decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 8 --max-new 32

A minimal production-shaped server core: a request queue, token-by-token
prefill, a decode batch with in-flight slot reuse (a finished request's
slot is refilled from the queue), greedy sampling.  ``--smoke`` serves the
CPU-sized config in f32; without it the registered full config runs in its
own dtype.  Attention runs through the jnp path of ``repro.models``; no
Pallas kernel is on this path yet.

Each request's model input is its prompt and then its served tokens, each
fed once at its own position: ``admit`` prefills all but the prompt's last
token, and the first decode step feeds that token and samples the first
served one.

Tracing: ``admit`` and ``step`` open ``jax.profiler.TraceAnnotation``
spans (``serve.admit`` > ``serve.prefill``; ``serve.step`` >
``serve.decode``, ``serve.sample``), which a profiler trace records on the
clock of the device's ops and cost nothing measurable without one.  With a
``repro.obs.Probe`` the server also counts prefill tokens, decode steps,
active slots summed over steps, served tokens and completed requests,
under the names ``repro.serve_sim``'s simulator uses.

Decode steps run with **per-slot cache positions**: each active slot
writes/attends at its own sequence position, so slots at different depths
coexist in one batch (the scalar-``pos`` variant corrupted any slot that
was not at ``max(slot_pos)``).

This server is the *measured* counterpart of the virtual
continuous-batching scheduler in ``repro.serve_sim.scheduler`` — it logs
the same per-request TTFT/TPOT and an admit/step/finish event sequence, so
the paper's predicted-vs-measured accuracy loop extends to serving
(``tests/test_serve_sim.py`` asserts the virtual scheduler reproduces this
loop's ordering on a scripted arrival trace).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import get_arch
from repro.launch import common
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import api
from repro.obs import Probe
from repro.sharding import activation_rules

# host spans, on the profiler's clock
SPAN_ADMIT = "serve.admit"
SPAN_PREFILL = "serve.prefill"      # inside serve.admit
SPAN_STEP = "serve.step"
SPAN_DECODE = "serve.decode"        # inside serve.step: dispatch to logits
SPAN_SAMPLE = "serve.sample"        # inside serve.step: argmax, bookkeeping
SPANS = (SPAN_ADMIT, SPAN_PREFILL, SPAN_STEP, SPAN_DECODE, SPAN_SAMPLE)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False
    # False once any logits row this request sampled from was not finite
    finite: bool = True
    # per-request serving metrics (perf_counter timestamps; the measured
    # side of the virtual ServingReport)
    t_arrive: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_arrive

    @property
    def tpot(self) -> float:
        n = len(self.out)
        return (self.t_done - self.t_first) / (n - 1) if n > 1 else 0.0


class BatchedServer:
    """Slot-based continuous batching (decode-centric).

    ``decode_fn(params, state, tokens, pos) -> (logits, state)`` defaults
    to the jitted JAX decode step; tests inject a stub to exercise the
    scheduling loop (admit/step ordering, per-slot positions) without
    compiling a model.  ``pos`` is always the per-slot position vector.
    """

    def __init__(self, cfg, batch_slots: int, max_len: int,
                 decode_fn: Optional[Callable] = None, state=None,
                 record_events: bool = False, probe: Optional[Probe] = None):
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.record_events = record_events
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)
        if decode_fn is None:
            self.state = api.allocate_decode_state(cfg, batch_slots, max_len)
            self.decode = jax.jit(steps_lib.make_serve_step(cfg),
                                  donate_argnums=(1,))
        else:
            self.state = state
            self.decode = decode_fn
        self.params = None
        # ("admit", rid) | ("step", rids) | ("finish", rid); recorded only
        # with record_events (parity vs the virtual scheduler) — unbounded
        # otherwise
        self.events: List[Tuple] = []
        # counter handles, bound once; each site pays one ``is not None``
        self.probe = probe
        if probe is not None:
            self._p_prefill = probe.counter("serve/prefill_tokens",
                                            unit="tokens")
            self._p_steps = probe.counter("serve/decode_steps", unit="steps")
            self._p_slot_steps = probe.counter("serve/slot_steps",
                                               unit="slots")
            self._p_tokens = probe.counter("serve/tokens_out", unit="tokens")
            self._p_completed = probe.counter("serve/completed",
                                              unit="requests")
        else:
            self._p_prefill = self._p_steps = self._p_slot_steps = None
            self._p_tokens = self._p_completed = None

    def load(self, params):
        self.params = params

    def _pos_vector(self, slot: int, pos: int) -> np.ndarray:
        """Per-slot positions: every slot keeps its own write index; only
        ``slot`` is overridden (prefill walks it through the prompt)."""
        vec = self.slot_pos.copy()
        vec[slot] = pos
        return vec

    def admit(self, req: Request) -> bool:
        """Prefill a request into a free slot (token-by-token prefill keeps
        one compiled decode step; bucket prefill is the production path).
        The prompt's last token is left for the first decode step, which
        feeds it at position ``len(prompt) - 1``."""
        n = len(req.prompt)
        with jax.profiler.TraceAnnotation(SPAN_ADMIT, rid=req.rid,
                                          prompt_len=n):
            try:
                slot = self.slot_req.index(None)
            except ValueError:
                return False
            self.slot_req[slot] = req
            req.t_admit = time.perf_counter()
            if self.record_events:
                self.events.append(("admit", req.rid))
            with jax.profiler.TraceAnnotation(SPAN_PREFILL):
                for pos, tok in enumerate(req.prompt[:-1]):
                    tokens = np.zeros((self.slots,), np.int32)
                    tokens[slot] = tok
                    _, self.state = self.decode(
                        self.params, self.state, jnp.asarray(tokens),
                        jnp.asarray(self._pos_vector(slot, pos), jnp.int32))
            self.slot_pos[slot] = n - 1
            if self._p_prefill is not None:
                self._p_prefill.add(self.probe.elapsed(), n - 1)
            return True

    def step(self) -> int:
        """One decode step for every active slot; returns #finished."""
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
            if not active:
                return 0
            tokens = np.zeros((self.slots,), np.int32)
            for i in active:
                r = self.slot_req[i]
                tokens[i] = r.out[-1] if r.out else r.prompt[-1]
            if self.record_events:
                self.events.append(
                    ("step", tuple(sorted(self.slot_req[i].rid
                                          for i in active))))
            with jax.profiler.TraceAnnotation(SPAN_DECODE):
                logits, self.state = self.decode(
                    self.params, self.state, jnp.asarray(tokens),
                    jnp.asarray(self.slot_pos, jnp.int32))
                logits = np.asarray(logits)
            with jax.profiler.TraceAnnotation(SPAN_SAMPLE):
                finished = self._sample(active, logits)
            if self._p_steps is not None:
                t = self.probe.elapsed()
                self._p_steps.add(t)
                self._p_slot_steps.add(t, len(active))
                self._p_tokens.add(t, len(active))
                self._p_completed.add(t, finished)
            return finished

    def _sample(self, active: List[int], logits: np.ndarray) -> int:
        """Greedy tokens for the active slots; frees finished slots and
        returns how many finished."""
        now = time.perf_counter()
        finished = 0
        for i in active:
            r = self.slot_req[i]
            nxt = int(np.argmax(logits[i]))
            r.finite = r.finite and bool(np.isfinite(logits[i]).all())
            if not r.out:
                r.t_first = now
            r.out.append(nxt)
            self.slot_pos[i] += 1
            if len(r.out) >= r.max_new or self.slot_pos[i] >= self.max_len - 1:
                r.done = True
                r.t_done = now
                self.slot_req[i] = None
                if self.record_events:
                    self.events.append(("finish", r.rid))
                finished += 1
        return finished


def serve_summary(requests: List[Request]) -> str:
    """Measured TTFT/TPOT percentiles (counterpart of ServingReport)."""
    done = [r for r in requests if r.done]
    if not done:
        return "no finished requests"
    ttft = np.array([r.ttft for r in done])
    tpot = np.array([r.tpot for r in done if len(r.out) > 1])
    lines = [f"  TTFT p50/p99 = {np.percentile(ttft, 50) * 1e3:.0f}/"
             f"{np.percentile(ttft, 99) * 1e3:.0f} ms"]
    if tpot.size:
        lines.append(f"  TPOT p50/p99 = {np.percentile(tpot, 50) * 1e3:.2f}/"
                     f"{np.percentile(tpot, 99) * 1e3:.2f} ms")
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--max-len", type=int, default=128)
    args = p.parse_args(argv)

    common.enable_compile_cache()
    cfg = common.run_config(get_arch(args.arch), args.smoke)
    if cfg.family in ("audio", "encdec", "convnet"):
        raise SystemExit("serve.py targets decoder-only archs")

    mesh = mesh_lib.make_elastic_mesh(jax.device_count(), 1)
    with activation_rules(mesh):
        params = api.init_params(jax.random.key(0), cfg)
        probe = Probe("serve")
        server = BatchedServer(cfg, args.slots, args.max_len, probe=probe)
        server.load(params)

        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        queue = [Request(i, rng.integers(0, cfg.vocab_size,
                                         size=(args.prompt_len,)),
                         args.max_new, t_arrive=t0)
                 for i in range(args.requests)]
        done: List[Request] = []
        pending = list(queue)
        while len(done) < len(queue):
            while pending and server.admit(pending[0]):
                pending.pop(0)
            server.step()
            done = [r for r in queue if r.done]
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in queue)
        c = probe.to_metrics()["counters"]
        steps = int(c["serve/decode_steps"])
        print(f"served {len(queue)} requests, {toks} tokens in {wall:.2f}s "
              f"({toks / wall:.1f} tok/s, {steps} decode steps)")
        print(serve_summary(queue))
        print("  counters: " + ", ".join(f"{k.split('/', 1)[1]} {int(v)}"
                                         for k, v in c.items()))
        occ = c["serve/slot_steps"] / max(steps * args.slots, 1)
        print(f"  slot occupancy = {occ:.3f}")
        return queue


if __name__ == "__main__":
    main()
