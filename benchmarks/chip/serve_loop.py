"""Serving loop: an open loop around the program's ``BatchedServer``.

Requests come due on the mix's schedule (``traffic_gen.serve_schedule``),
whatever the server does.  The loop asks ``BatchedServer.admit`` to prefill
each due request while a slot is free, and ``step`` to decode every active
slot, and records each request's due, admit, first-token and token times
on the host clock.  After the window, arrivals go on as scheduled until
every request due in the window has its first token, or until one more
window has passed; a request still waiting then counts as failed and
enters the TTFT sample at that limit.

The check runs after the window and after the program's state is freed:
for a sample of finished requests drawn from the seed (the longest among
them), the configuration's reference (``harness.reference_for``) reads
the prompt and the served tokens, and ``gap`` is the widest amount by
which a served token's logit lies below the reference's best at its
position.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import harness
import traffic_gen
import weights

SAMPLE_TOKENS = 300       # served tokens the check reads, at least
SAMPLE_MAX = 16           # requests the check reads, at most


def build(cell, seed: int):
    """The server for the cell, loaded with the seed's weights and warmed
    up on the cell's one decode shape."""
    from repro.launch.serve import BatchedServer, Request
    from repro.models import api

    cfg = harness.program_config(cell.config, cell.traffic)
    mix = cell.traffic
    server = BatchedServer(cfg, cell.config["serve_slots"], mix["max_len"])
    shapes = api.param_shapes(cfg)
    server.load(weights.make_params(shapes, seed))
    warm = Request(-1, np.zeros(1, np.int32), 1)
    server.admit(warm)
    server.step()
    return cfg, server, shapes


def serve(server, plan: List, window_s: float, trace: bool):
    """The open loop.  Returns the requests (program objects), their token
    times, the work log of every dispatch, the window's start, how late
    the generator ran, and the admission queue's length over time."""
    from repro.launch.serve import Request

    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    reqs, toks = [], {}
    work: List = []             # (kind, t, [live lengths]) per dispatch
    pending = collections.deque()
    late, nxt, qlog = 0.0, 0, []
    n_window = sum(p.due < window_s for p in plan)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        with span("gen"):
            while nxt < len(plan) and plan[nxt].due <= now:
                p = plan[nxt]
                r = Request(p.rid, p.prompt, p.max_new, t_arrive=t0 + p.due)
                reqs.append(r)
                toks[r.rid] = []
                pending.append(r)
                late = max(late, now - p.due)
                nxt += 1
        qlog.append((now, len(pending)))
        waiting = [r for r in reqs[:n_window] if not r.out]
        if now >= window_s and ((nxt >= n_window and not waiting)
                                or now >= 2 * window_s):
            break
        while pending and None in server.slot_req:
            r = pending[0]
            with span("admit"):
                server.admit(r)
            pending.popleft()
            work.append(("admit", r.t_admit, list(range(len(r.prompt)))))
        active = [r for r in server.slot_req if r is not None]
        if active:
            ctx = [int(server.slot_pos[i]) for i, r in
                   enumerate(server.slot_req) if r is not None]
            t_call = time.perf_counter()
            with span("step"):
                server.step()
            t = time.perf_counter()
            work.append(("step", t_call, ctx))
            for r in active:
                toks[r.rid].append(t)
        elif nxt < len(plan):
            time.sleep(max(0.0, min(plan[nxt].due - now, 0.005)))
        else:
            break
    return reqs, toks, work, t0, late, qlog


def metrics(reqs, toks, t0: float, window_s: float, t_stop: float) -> Dict:
    end = t0 + window_s
    win = [r for r in reqs if r.t_arrive < end]
    ttft, failed = [], 0
    for r in win:
        if r.out:
            ttft.append(r.t_first - r.t_arrive)
        else:
            failed += 1
            ttft.append(t_stop - r.t_arrive)
    gaps = [b - a for r in win for a, b in zip(toks[r.rid], toks[r.rid][1:])
            if t0 <= b < end]
    out_tok = sum(t0 <= x < end for ts in toks.values() for x in ts)
    q = lambda xs, p: float(np.percentile(xs, p)) * 1e3 if xs else float("nan")
    return {"ttft_p95_ms": q(ttft, 95), "itl_p95_ms": q(gaps, 95),
            "out_tok_s": out_tok / window_s, "attempted": len(win),
            "failed": failed,
            "queue_wait_ms": [1e3 * (r.t_admit - r.t_arrive) for r in win
                              if r.t_admit],
            "prefill_ms": [1e3 * (r.t_first - r.t_admit) for r in win
                           if r.out]}


def run(cell, seed: int, seconds: float, trace_dir, clock, t_start) -> Dict:
    mix = cell.traffic
    cfg, server, shapes = build(cell, seed)
    plan = traffic_gen.serve_schedule(mix, seed, 2 * seconds + 1.0,
                                      cfg.vocab_size)
    jax.block_until_ready(server.state)
    rec: Dict = {"kind": "serve", "slots": server.slots,
                 "setup_s": time.perf_counter() - t_start,
                 "setup_compile": clock.lap()}
    prof = jax.profiler.trace(trace_dir) if trace_dir else \
        contextlib.nullcontext()
    with prof:
        reqs, toks, work, t0, late, _ = serve(server, plan, seconds,
                                           bool(trace_dir))
    t_stop = time.perf_counter()
    rec["window_compile"] = clock.lap()
    m = metrics(reqs, toks, t0, seconds, t_stop)
    rec.update(m, steps=m["attempted"], window_s=seconds, work=work, t0=t0,
               generator_late_s=late)
    rec["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])
    done = [r for r in reqs if r.done and r.t_arrive < t0 + seconds]
    sample = pick_sample(done, seed)
    served = [(np.asarray(r.prompt), list(r.out)) for r in sample]
    del server, reqs
    gc.collect()
    t_ref = time.perf_counter()
    rec["check"] = reference_gaps(cell, shapes, seed, served, mix["max_len"])
    rec["reference_s"] = time.perf_counter() - t_ref
    rec["checked_tokens"] = sum(len(o) for _, o in served)
    return rec


def pick_sample(done: List, seed: int) -> List:
    """The longest finished request and others drawn from the seed, until
    ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX`` requests."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 1])
    longest = max(done, key=lambda r: len(r.out))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.out)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.out)
    return out


def _padded(tokens, rows, chosen, length: int):
    t = np.zeros(length, np.int32)
    t[:len(tokens)] = tokens
    rr = np.zeros(length, np.int32)
    rr[:len(rows)] = rows
    cc = np.zeros(length, np.int32)
    cc[:len(chosen)] = chosen
    return jnp.asarray(t), jnp.asarray(rr), jnp.asarray(cc)


def reference_gaps(cell, shapes, seed: int, served, max_len: int,
                   mm=None) -> Dict:
    """For each (prompt, served tokens): the gap of every served token
    under the reference over prompt + served tokens (``gap``), and, as a
    diagnostic, over the sequence the server's loop fed its model, which
    repeats the prompt's last token (``gap_fed``).  With ``mm`` = the
    reference's ``fp8_mm``, ``control`` is the gap, under the float32
    reference, of the token the lower precision puts first."""
    reference = harness.reference_for(cell.config, cell.here)
    mm = mm or reference.highest_mm
    rc = reference.RefConfig.from_file(cell.config)
    out = {"gap": 0.0, "gap_fed": 0.0, "control": 0.0}
    if not served:
        return out
    with jax.default_matmul_precision("highest"):
        W = weights.cast_tree(weights.make_params(shapes, seed), jnp.float32)
        for prompt, gen in served:
            P, n = len(prompt), len(gen)
            seq = np.concatenate([prompt, gen[:-1]]).astype(np.int32)
            t, rows, ch = _padded(seq, np.arange(P - 1, P - 1 + n), gen,
                                  max_len)
            g = np.asarray(reference.gaps_at(reference.highest_mm, rc, W,
                                             t, rows, ch))[:n]
            out["gap"] = max(out["gap"], float(g.max()))
            fed = np.concatenate([prompt, prompt[-1:], gen[:-1]])
            t2, rows2, ch2 = _padded(fed, np.arange(P, P + n), gen, max_len)
            g2 = np.asarray(reference.gaps_at(reference.highest_mm, rc, W,
                                              t2, rows2, ch2))[:n]
            out["gap_fed"] = max(out["gap_fed"], float(g2.max()))
            if mm is not reference.highest_mm:
                top = reference.argmax_at(mm, rc, W, t, rows)
                g3 = np.asarray(reference.gaps_at(reference.highest_mm, rc,
                                                  W, t, rows, top))[:n]
                out["control"] = max(out["control"], float(g3.max()))
    return out


def readings(rec) -> Dict:
    return {"gap": rec["check"]["gap"],
            "_gap_over_fed_sequence": rec["check"]["gap_fed"],
            "_checked_tokens": rec["checked_tokens"],
            "_generator_late_s": rec["generator_late_s"],
            "_slots": rec["slots"]}
