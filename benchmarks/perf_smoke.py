"""CI perf-smoke: fail if simulation-core throughput regresses.

Runs the DES and serve-sim microbenchmarks and enforces conservative
floors — roughly two thirds of the throughput measured on the PR 8 tree
on a quiet container — so ordinary CI-machine variance passes but a
reintroduced O(n^2) hot path or per-task object churn fails loudly.
All scenarios run with ``probe=None``, so these floors also guard the
observability layer's disabled-path contract (one dead branch per hot
site, nothing else):

  * fifo static fast path (warm cache) >= 300k events/s
    (measured: ~400-615k)
  * shared-channel burst, n=3200       >= 120k tasks/s
    (seed: ~2.3k — the quadratic collapse; measured: ~140-260k)
  * shared-channel flatness n=6400/200 >= 0.3
    (quadratic scaling gives ~0.12: completions per burst grow 32x while
    per-event cost also grows 32x)
  * serve_sim 10k requests             >= 17k req/wall-s
    (seed: ~1.9k; measured: ~26k)
  * dynamic injection, fast engine     >= 420k events/s
    (the array-backed ``DynamicSimulator`` + template instantiation;
    measured ~700k)
  * serve_sim 10k, speculative leap    >= 15k req/wall-s
    (a ``decode_stable``-only scheduler: every decode fusion takes the
    snapshot/rollback path; measured ~23k)
  * serve_sim 10k, task-graph mode     >= 12k req/wall-s
    (``TemplateLane`` graph serving, 4 chunks + KV writes per phase;
    measured ~16-22k — per-chunk event injection sustained ~3k-11k on
    the same scenario, so a lost burst/closed-form path fails loudly)
  * serve_sim 10k, graph speculative   >= 11k req/wall-s
    (task-graph mode under the ``decode_stable``-only scheduler: every
    leap is one ``TemplateLane`` burst with snapshot rollback)
  * monte-carlo seed batch, 16 x 10k   >= 80k seed-requests/wall-s
    (PR 6's fused continuous-batching fast path at replicas=4 slots=32,
    300 rps Poisson; measured: ~108-128k — the scalar loop over the
    same rows sustains ~20k, so this floor also guards the >= 5x
    headline)

Exit code 0 on pass, 1 on any floor violation.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FLOORS = {
    "fifo_static_warm_events_per_sec": 300_000.0,
    "shared_3200_tasks_per_sec": 120_000.0,
    "shared_flatness_6400_over_200": 0.3,
    "serve_sim_requests_per_sec": 17_000.0,
    "dynamic_injection_fast_events_per_sec": 420_000.0,
    "serve_sim_speculative_requests_per_sec": 15_000.0,
    "serve_sim_taskgraph_requests_per_sec": 12_000.0,
    "serve_sim_taskgraph_speculative_requests_per_sec": 11_000.0,
    "monte_carlo_seed_requests_per_sec": 80_000.0,
}


def _taskgraph_requests_per_sec(speculative: bool) -> float:
    """10k requests in full task-graph mode (``TemplateLane``
    serving), best-of-2.  ``speculative`` swaps in the
    ``decode_stable``-only scheduler so every leap takes the burst
    snapshot/rollback path."""
    from benchmarks.bench_serve_sim import SpeculativeContinuousScheduler
    from benchmarks.perf_record import _serve_cost, _traffic
    from repro.serve_sim import ContinuousBatchingScheduler, ServingSimulator

    cost = _serve_cost()
    sched = (SpeculativeContinuousScheduler if speculative
             else ContinuousBatchingScheduler)
    wall = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        rep = ServingSimulator(cost, sched, _traffic(), replicas=4,
                               slots=8, phase_tasks=4).run()
        wall = min(wall, time.perf_counter() - t0)
    return rep.n_requests / wall


def _monte_carlo_seed_requests_per_sec() -> float:
    """16 seeds x 10k requests through the fused MC fast path, as
    (seeds x requests) per wall second."""
    from benchmarks.perf_record import _serve_cost
    from repro.serve_sim import (ContinuousBatchingScheduler, LengthDist,
                                 MonteCarloServingSimulator,
                                 poisson_workload_batch)

    cost = _serve_cost()
    seeds, n = 16, 10_000
    batch = poisson_workload_batch(300.0, n,
                                   prompt=LengthDist(mean=512, cv=0.6),
                                   output=LengthDist(mean=96, cv=0.5),
                                   seeds=seeds)
    sim = MonteCarloServingSimulator(cost, ContinuousBatchingScheduler,
                                     batch, replicas=4, slots=32)
    assert sim.fast_path, "smoke scenario must hit the fused fast path"
    t0 = time.perf_counter()
    sim.run()
    return seeds * n / (time.perf_counter() - t0)


def main() -> int:
    from benchmarks import bench_engine
    from benchmarks.perf_record import (_serve_sim_10k,
                                        _serve_sim_10k_speculative)

    measured = {}
    fifo = bench_engine.fifo_events_per_sec()
    measured["fifo_static_warm_events_per_sec"] = fifo["static_warm"]
    shared = bench_engine.shared_tasks_per_sec()
    measured["shared_3200_tasks_per_sec"] = shared["3200"]
    measured["shared_flatness_6400_over_200"] = \
        shared["6400"] / shared["200"]
    measured["dynamic_injection_fast_events_per_sec"] = \
        bench_engine.dynamic_events_per_sec()["fast"]
    serve = _serve_sim_10k()
    measured["serve_sim_requests_per_sec"] = serve["requests_per_sec"]
    spec = _serve_sim_10k_speculative()
    measured["serve_sim_speculative_requests_per_sec"] = \
        spec["requests_per_sec"]
    measured["serve_sim_taskgraph_requests_per_sec"] = \
        _taskgraph_requests_per_sec(speculative=False)
    measured["serve_sim_taskgraph_speculative_requests_per_sec"] = \
        _taskgraph_requests_per_sec(speculative=True)
    measured["monte_carlo_seed_requests_per_sec"] = \
        _monte_carlo_seed_requests_per_sec()

    failed = False
    for key, floor in FLOORS.items():
        got = measured[key]
        status = "ok " if got >= floor else "FAIL"
        if got < floor:
            failed = True
        print(f"[{status}] {key}: {got:,.1f} (floor {floor:,.1f})")
    return 1 if failed else 0


if __name__ == "__main__":
    t0 = time.perf_counter()
    rc = main()
    print(f"perf-smoke finished in {time.perf_counter() - t0:.1f}s -> "
          f"{'FAIL' if rc else 'PASS'}")
    sys.exit(rc)
