"""DES-engine microbenchmarks: event throughput and contention scaling.

Measures the simulation core this PR optimized:

  * fifo event throughput — a layered 10k-task DAG over 4 FIFO resources
    on the array-backed static engine (cold cache = first sweep point,
    warm cache = steady-state what-if loop);
  * shared-channel scaling — n concurrent transfers with distinct
    durations on one width-2 processor-sharing channel.  Virtual-time GPS
    completes each in O(log n); the seed engine's per-event remaining-work
    sweep was O(n), i.e. O(n^2) per burst, so its throughput collapsed
    with n (see ``BASELINE_PR2`` in ``perf_record.py`` for the measured
    collapse: 10.6k -> 1.3k tasks/s from n=200 to n=6400).  Acceptance:
    throughput stays roughly flat with n.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from repro.core.sim.engine import (DynamicSimulator, GraphTemplate,
                                   ResourceSpec, StaticCache, Task,
                                   simulate_static)

SHARED_NS = (200, 800, 3200, 6400)


def layered_dag(n_layers: int = 200, width: int = 50) -> List[Task]:
    """A deep, wide DAG: each task depends on two tasks of the previous
    layer and lands on one of four FIFO resources."""
    tasks: List[Task] = []
    tid = 0
    prev: List[int] = []
    for layer in range(n_layers):
        cur = []
        for w in range(width):
            tasks.append(Task(tid, f"t{tid}", f"L{layer}", f"r{w % 4}",
                              1e-6, deps=tuple(prev[:2])))
            cur.append(tid)
            tid += 1
        prev = cur
    return tasks


def shared_burst(n: int) -> Tuple[List[Task], Dict[str, ResourceSpec]]:
    """n concurrent transfers with distinct durations on one shared
    channel — the worst case for per-event remaining-work bookkeeping."""
    tasks = [Task(i, f"s{i}", "L", "link", (i + 1) * 1e-6) for i in range(n)]
    specs = {"link": ResourceSpec("link", servers=2, mode="shared")}
    return tasks, specs


def _best_of(fn, reps: int = 3) -> float:
    """Minimum wall time over ``reps`` runs (stable against CI noise)."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def fifo_events_per_sec() -> Dict[str, float]:
    tasks = layered_dag()
    n = len(tasks)
    t_cold = _best_of(lambda: simulate_static(tasks))
    cache = StaticCache(tasks)
    t_warm = _best_of(lambda: simulate_static(tasks, cache=cache))
    return {"static_cold": n / t_cold, "static_warm": n / t_warm}


def shared_tasks_per_sec() -> Dict[str, float]:
    out = {}
    for n in SHARED_NS:
        tasks, specs = shared_burst(n)
        out[str(n)] = n / _best_of(lambda: simulate_static(tasks, specs))
    return out


def dynamic_events_per_sec(n_phases: int = 3000,
                           chunks: int = 4) -> Dict[str, float]:
    """Traffic-style dynamic injection: phases of ``chunks`` chained
    compute tasks plus zero-cost KV writes, each phase injected when the
    previous one completes — the serving simulator's task-graph pattern
    without the scheduler, isolating the injection overhead of
    ``DynamicSimulator.inject_template``."""
    n_tasks = n_phases * 2 * chunks
    tpl_tasks = []
    for i in range(chunks):
        tpl_tasks.append(Task(2 * i, "c", "rep", "rep", 0.0,
                              deps=(2 * i - 2,) if i else ()))
        tpl_tasks.append(Task(2 * i + 1, "kv", "kv", "rep:kv", 0.0,
                              deps=(2 * i,)))
    tpl = GraphTemplate(tpl_tasks, tail=2 * chunks - 2)
    durs = [1e-6, 0.0] * chunks

    def run_fast() -> None:
        sim = DynamicSimulator()
        done = [0]

        def submit(now: float = 0.0) -> None:
            if done[0] >= n_phases:
                return
            done[0] += 1
            sim.inject_template(tpl, durs, on_done=submit)

        sim.at(0.0, submit)
        sim.run()

    return {"fast": n_tasks / _best_of(run_fast)}


def run() -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    fifo = fifo_events_per_sec()
    rows.append(("engine_fifo_10k", 1e6 * 10_000 / fifo["static_warm"],
                 f"static_cold={fifo['static_cold']:.0f}ev/s "
                 f"static_warm={fifo['static_warm']:.0f}ev/s"))
    shared = shared_tasks_per_sec()
    lo, hi = str(SHARED_NS[0]), str(SHARED_NS[-1])
    rows.append((
        "engine_shared_scaling",
        1e6 * SHARED_NS[-1] / shared[hi],
        " ".join(f"n{k}={v:.0f}/s" for k, v in shared.items())
        + f" flatness={shared[hi] / shared[lo]:.2f}"
        " (accept: >0.3; the seed engine collapsed to 0.12)"))
    dyn = dynamic_events_per_sec()
    rows.append((
        "engine_dynamic_injection",
        1e6 * 24_000 / dyn["fast"],
        f"fast={dyn['fast']:.0f}ev/s"))
    return rows
