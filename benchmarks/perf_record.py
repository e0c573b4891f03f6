"""Machine-readable perf trajectory: writes ``BENCH_pr10.json``.

This PR adds resilient cluster serving: heterogeneous ``ReplicaPool``\\ s
behind a pluggable routing tier with health-checked rotation, cross-pool
failover, latency hedging, circuit breakers and reactive autoscaling.
The headline metric is the new ``cluster_1m_chaos`` scenario — one
million requests through a 72-replica, 3-zone cluster under live
MTBF/MTTR churn with health checks and failover, in a single
``ClusterSimulator`` run; the companion gate is
``benchmarks/cluster_smoke.py``, which pins seeded determinism, 1-pool
golden parity with the standalone simulator, and a < 10% routing-tier
overhead bound::

    PYTHONPATH=src python benchmarks/run.py --json        # BENCH_pr10.json
    PYTHONPATH=src python benchmarks/perf_record.py       # same, standalone
    PYTHONPATH=src python benchmarks/perf_record.py --trials 3   # medians

``BASELINE_PR9`` is the ``current`` section of the committed
``BENCH_pr9.json``; absolute numbers are machine-dependent, the *ratios*
are the tracked signal.  Paired comparisons (MC vs scalar loop,
probe-on vs probe-off) are measured interleaved in this process, so
load drifts hit both sides.  The ``--trials N`` median mode
exists so recordings are robust to a single bad window: each trial runs
the full suite, and every leaf metric reports the across-trial median.
"""
from __future__ import annotations

import json
import platform
import statistics
import sys
import time
from typing import Dict, List

# The "current" section of BENCH_pr9.json, measured at db7ec02 (PR 9).
BASELINE_PR9: Dict = {
    "engine_fifo_events_per_sec": {"static_cold": 395160.8601, "static_warm": 590498.0828},
    "engine_shared_tasks_per_sec": {"200": 315746.7647, "800": 281400.3184, "3200": 261530.9186, "6400": 237899.9265},
    "engine_dynamic_injection_events_per_sec": {"fast": 753716.5291},
    "what_if_points_per_sec": {"roofline": 2145.7852, "analytic": 1558.9365, "des": 40.1664},
    "serve_sim_10k": {"wall_seconds": 0.3341, "requests_per_sec": 29928.7271},
    "serve_sim_10k_taskgraph": {"fast_wall_seconds": 0.4534, "fast_requests_per_sec": 22055.8395},
    "serve_sim_10k_speculative": {"wall_seconds": 0.3215, "requests_per_sec": 31100.726},
    "serve_sim_10k_taskgraph_speculative": {"wall_seconds": 0.4163, "requests_per_sec": 24022.497},
    "serve_sim_10k_chaos": {"wall_seconds": 0.1035, "requests_per_sec": 94650.2414, "availability": 0.9128, "n_failures": 69, "n_retries": 338, "n_abandoned": 207},
    "monte_carlo": {"seeds": 64, "requests_per_seed": 10000, "scalar_ref_seeds": 8, "mc_wall_seconds": 4.5762, "scalar_loop_wall_seconds_est": 30.7519, "mc_seed_requests_per_sec": 139855.2623, "scalar_seed_requests_per_sec": 20811.7361, "speedup_mc_vs_scalar_loop": 6.5956, "sweep_point_slots": 256, "sweep_single_seed_seconds": 1.4074, "sweep_64seed_seconds": 3.4171, "sweep_64seed_cost_vs_single": 2.4332},
    "persistent_pool": {"explore_serial_seconds": 0.1645, "explore_first_call_seconds": 0.6066, "explore_steady_call_seconds": 0.0965, "steady_vs_first_speedup": 6.2837},
    "obs_overhead": {"off_wall_seconds": 0.3493, "sampled_wall_seconds": 0.3794, "full_wall_seconds": 0.5884, "sampled_overhead_pct": 7.8482, "full_overhead_pct": 72.3129},
}


def _what_if_points_per_sec() -> Dict[str, float]:
    import numpy as np

    from repro.core.config import LM_SHAPES, get_arch
    from repro.core.dse import DesignSpaceExplorer
    from repro.core.hw import tpu_v5e_pod
    from repro.core.taskgraph.builders import ShardPlan, lm_step_ops

    spec = get_arch("qwen1.5-0.5b")
    ops = lm_step_ops(spec.model, LM_SHAPES["train_4k"], ShardPlan())
    base = tpu_v5e_pod()
    dse = DesignSpaceExplorer({"w": ops})
    dse.compiled("w", base).anno_arrays()       # steady-state sweep loop
    values = list(np.linspace(50e9, 200e9, 16))
    out = {}
    for backend in ("roofline", "analytic", "des"):
        t0 = time.perf_counter()
        dse.what_if_sweep("w", base, "link_bandwidth", values,
                          backend=backend)
        out[backend] = len(values) / (time.perf_counter() - t0)
    return out


def _serve_cost() -> object:
    from repro.core.config import get_arch
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.core.taskgraph.builders import ShardPlan
    from repro.serve_sim import ServingCostModelBuilder

    cfg = get_arch("qwen1.5-0.5b").model
    base = SystemDescription(name="v5e_chip", chip=tpu_v5e_chip(), torus=())
    return ServingCostModelBuilder(
        cfg, shard=ShardPlan(data=1, model=1)).model_for(base)


def _traffic(n=10_000):
    from repro.serve_sim import LengthDist, poisson_workload

    return poisson_workload(120.0, n,
                            prompt=LengthDist(mean=512, cv=0.6),
                            output=LengthDist(mean=96, cv=0.5), seed=0)


def _serve_sim_10k() -> Dict[str, float]:
    import gc

    from repro.serve_sim import ContinuousBatchingScheduler, simulate_serving

    cost = _serve_cost()
    wall = float("inf")
    for _ in range(2):
        gc.collect()
        t0 = time.perf_counter()
        rep = simulate_serving(cost, ContinuousBatchingScheduler, _traffic(),
                               replicas=4, slots=8)
        wall = min(wall, time.perf_counter() - t0)
    return {"wall_seconds": wall, "requests_per_sec": rep.n_requests / wall}


def _serve_sim_10k_taskgraph(reps: int = 3) -> Dict[str, float]:
    """10k requests in full task-graph mode (4 chunks + KV writes per
    phase), best-of-``reps``."""
    from repro.serve_sim import ContinuousBatchingScheduler, ServingSimulator

    import gc

    cost = _serve_cost()
    wall = float("inf")
    for _ in range(reps):
        gc.collect()                         # drain prior suites' garbage
        t0 = time.perf_counter()
        rep = ServingSimulator(cost, ContinuousBatchingScheduler,
                               _traffic(), replicas=4, slots=8,
                               phase_tasks=4).run()
        wall = min(wall, time.perf_counter() - t0)
    return {"fast_wall_seconds": wall,
            "fast_requests_per_sec": rep.n_requests / wall}


def _serve_sim_10k_speculative() -> Dict[str, float]:
    """10k requests under a scheduler declaring only ``decode_stable``:
    every decode leap is speculative (snapshot + rollback on arrivals) —
    the non-``steady_decode`` case that previously ran per-step."""
    import gc

    from benchmarks.bench_serve_sim import SpeculativeContinuousScheduler
    from repro.serve_sim import simulate_serving

    cost = _serve_cost()
    wall = float("inf")
    for _ in range(2):
        gc.collect()
        t0 = time.perf_counter()
        rep = simulate_serving(cost, SpeculativeContinuousScheduler,
                               _traffic(), replicas=4, slots=8)
        wall = min(wall, time.perf_counter() - t0)
    return {"wall_seconds": wall, "requests_per_sec": rep.n_requests / wall}


def _serve_sim_10k_taskgraph_speculative() -> Dict[str, float]:
    """10k requests with full task-graph injection under the
    decode_stable-only scheduler: every decode leap is booked as one
    ``TemplateLane`` burst of per-step template instances and rolled
    back (burst truncation at a snapshot boundary) when an arrival
    lands mid-leap — graph fidelity at lane-path speed."""
    import gc

    from benchmarks.bench_serve_sim import SpeculativeContinuousScheduler
    from repro.serve_sim import ServingSimulator

    cost = _serve_cost()
    wall = float("inf")
    for _ in range(2):
        gc.collect()
        t0 = time.perf_counter()
        rep = ServingSimulator(cost, SpeculativeContinuousScheduler,
                               _traffic(), replicas=4, slots=8,
                               phase_tasks=4).run()
        wall = min(wall, time.perf_counter() - t0)
    return {"wall_seconds": wall, "requests_per_sec": rep.n_requests / wall}


def _serve_sim_10k_chaos() -> Dict[str, float]:
    """10k requests on the fused fast path under live fault injection:
    MTBF=5s / MTTR=0.8s crash churn across 4 replicas with
    retry/backoff/deadline on every cancelled request.  The recorded
    availability / failure / retry counts are seeded and bit-stable;
    ``benchmarks/chaos_smoke.py`` separately bounds the armed-but-idle
    machinery cost on the no-fault scenario."""
    import gc

    from repro.serve_sim import FailureModel, RetryPolicy, compile_faults
    from repro.serve_sim.monte_carlo import _simulate_continuous_fast

    cost = _serve_cost()
    wl = _traffic()
    times = [r.t_arrive for r in wl.requests]
    prompts = [r.prompt_tokens for r in wl.requests]
    outputs = [r.output_tokens for r in wl.requests]
    failures = FailureModel(mtbf=5.0, mttr=0.8, seed=7, horizon=120.0)
    retry = RetryPolicy(max_attempts=4, backoff=0.02, deadline=30.0)
    cf = compile_faults(failures, 4, seed=(failures.seed, 0))
    wall = float("inf")
    for _ in range(2):
        gc.collect()
        t0 = time.perf_counter()
        rep = _simulate_continuous_fast(cost, times, prompts, outputs, 4, 8,
                                        "chaos", faults=cf, retry=retry)
        wall = min(wall, time.perf_counter() - t0)
    return {"wall_seconds": wall,
            "requests_per_sec": rep.n_requests / wall,
            "availability": rep.availability,
            "n_failures": rep.n_failures,
            "n_retries": rep.n_retries,
            "n_abandoned": rep.n_abandoned}


def _cluster_1m_chaos() -> Dict[str, float]:
    """One million requests through a 72-replica, 3-zone heterogeneous
    cluster under live fault churn: per-zone MTBF=60s / MTTR=5s crash
    processes, health-checked rotation (1s probes), least-loaded routing
    with cross-pool failover, all in a single ``ClusterSimulator`` run.
    Long-running by design — the acceptance point for this PR is that a
    fleet-scale scenario completes in one simulation, so it runs once
    per collect() pass (no inner best-of reps)."""
    import gc

    from repro.serve_sim import (ClusterSimulator, FailureModel,
                                 HealthCheckPolicy, LeastLoadedRouter,
                                 ReplicaPool, RetryPolicy, poisson_workload)

    cost = _serve_cost()
    pools = [ReplicaPool(f"zone-{z}", cost, 24, slots=16,
                         failures=FailureModel(mtbf=60.0, mttr=5.0,
                                               seed=10 + z, horizon=600.0),
                         retry=RetryPolicy())
             for z in range(3)]
    n = 1_000_000
    wl = poisson_workload(8000.0, n, seed=1)
    gc.collect()
    t0 = time.perf_counter()
    rep = ClusterSimulator(pools, wl, LeastLoadedRouter(retry_budget=4),
                           health=HealthCheckPolicy(interval=1.0)).run()
    wall = time.perf_counter() - t0
    return {"wall_seconds": wall,
            "requests_per_sec": rep.n_requests / wall,
            "replicas": rep.replicas,
            "sim_duration_seconds": rep.duration,
            "availability": rep.availability,
            "fleet_availability": rep.fleet_availability,
            "n_failures": rep.n_failures,
            "n_failovers": rep.n_failovers}


def _monte_carlo() -> Dict[str, float]:
    """Seed-batched Monte-Carlo serving vs looping the scalar simulator.

    Headline: 64 seeds x 10k requests through continuous batching
    (replicas=4, slots=32, 300 rps Poisson) in one
    ``MonteCarloServingSimulator`` call, against the scalar
    ``simulate_serving`` loop over the same seed rows — measured on
    ``scalar_ref_seeds`` rows and scaled linearly (per-seed scalar cost
    is independent across seeds).  Acceptance: the MC path sustains
    >= 5x (seeds x requests)/wall-second.

    Second check: one ``sweep_serving`` design point at slots=256 with
    ``num_seeds=64`` vs the single-seed point.  Acceptance: <= 3x —
    decode bursts dominate at large batch, and the MC fast path
    advances one in O(log slots) (packed completion heap) where the
    scalar simulator scans all slots.
    """
    import functools
    import gc

    from repro.core.config import get_arch
    from repro.core.dse import DesignSpaceExplorer
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.core.taskgraph.builders import ShardPlan
    from repro.core.taskgraph.ops import matmul_op
    from repro.serve_sim import (ContinuousBatchingScheduler, LengthDist,
                                 MonteCarloServingSimulator,
                                 ServingCostModelBuilder,
                                 poisson_workload, poisson_workload_batch,
                                 simulate_serving)

    cost = _serve_cost()
    dists = dict(prompt=LengthDist(mean=512, cv=0.6),
                 output=LengthDist(mean=96, cv=0.5))
    seeds, n = 64, 10_000
    batch = poisson_workload_batch(300.0, n, seeds=seeds, **dists)
    sim = MonteCarloServingSimulator(cost, ContinuousBatchingScheduler,
                                     batch, replicas=4, slots=32)
    assert sim.fast_path, "headline scenario must hit the fused fast path"
    gc.collect()
    t0 = time.perf_counter()
    sim.run()
    mc_wall = time.perf_counter() - t0
    ref = 8                                  # scalar loop sample (i.i.d.)
    gc.collect()
    t0 = time.perf_counter()
    for k in range(ref):
        simulate_serving(cost, ContinuousBatchingScheduler,
                         batch.workload(k), replicas=4, slots=32)
    scalar_wall = (time.perf_counter() - t0) * (seeds / ref)
    out = {
        "seeds": seeds, "requests_per_seed": n, "scalar_ref_seeds": ref,
        "mc_wall_seconds": mc_wall,
        "scalar_loop_wall_seconds_est": scalar_wall,
        "mc_seed_requests_per_sec": seeds * n / mc_wall,
        "scalar_seed_requests_per_sec": seeds * n / scalar_wall,
        "speedup_mc_vs_scalar_loop": scalar_wall / mc_wall,
    }

    # one sweep_serving design point: num_seeds=64 vs num_seeds=1
    base = SystemDescription(name="v5e_chip", chip=tpu_v5e_chip(), torus=())
    dse = DesignSpaceExplorer({"w": [matmul_op("m", "m", 64, 64, 64)]})
    builder = ServingCostModelBuilder(
        get_arch("qwen1.5-0.5b").model, shard=ShardPlan(data=1, model=1))
    sched = {"continuous": ContinuousBatchingScheduler}
    walls = {}
    gc.collect()
    for label, traffic, kw in (
            ("single", functools.partial(poisson_workload, 1000.0, n,
                                         seed=0, **dists), {}),
            ("mc64", functools.partial(poisson_workload_batch, 1000.0, n,
                                       seeds=seeds, **dists),
             {"num_seeds": seeds})):
        t0 = time.perf_counter()
        dse.sweep_serving({"v5e": base}, {"poisson": traffic}, sched,
                          cost_builder=builder, replicas=4, slots=256, **kw)
        walls[label] = time.perf_counter() - t0
    out.update({
        "sweep_point_slots": 256,
        "sweep_single_seed_seconds": walls["single"],
        "sweep_64seed_seconds": walls["mc64"],
        "sweep_64seed_cost_vs_single": walls["mc64"] / walls["single"],
    })
    return out


def _persistent_pool() -> Dict[str, float]:
    """Repeated ``explore(workers=4)`` sweeps: the first call pays the
    fork + structural-graph broadcast, later calls must show no per-call
    pool startup (they reuse workers and worker-side caches)."""
    from repro.core.avsm.model import annotate_system
    from repro.core.config import LM_SHAPES, get_arch
    from repro.core.dse import DesignSpaceExplorer
    from repro.core.hw import tpu_v5e_pod
    from repro.core.parallel import close_pools
    from repro.core.taskgraph.builders import ShardPlan, lm_step_ops

    spec = get_arch("qwen1.5-0.5b")
    ops = lm_step_ops(spec.model, LM_SHAPES["train_4k"], ShardPlan())
    base = tpu_v5e_pod()
    systems = {"base": base,
               "fast_mem": annotate_system(base, mem_bandwidth=1638e9),
               "fast_link": annotate_system(base, link_bandwidth=200e9),
               "slow_mem": annotate_system(base, mem_bandwidth=500e9)}
    dse = DesignSpaceExplorer({"w": ops})
    t0 = time.perf_counter()
    serial = dse.explore(systems, keep=4)
    t_serial = time.perf_counter() - t0
    close_pools()                            # measure a cold first call
    calls = []
    for _ in range(4):
        t0 = time.perf_counter()
        parallel = dse.explore(systems, keep=4, workers=4)
        calls.append(time.perf_counter() - t0)
    close_pools()
    assert [(r.system, r.confirmed.step_time) for r in serial] == \
        [(r.system, r.confirmed.step_time) for r in parallel]
    steady = min(calls[1:])
    return {"explore_serial_seconds": t_serial,
            "explore_first_call_seconds": calls[0],
            "explore_steady_call_seconds": steady,
            "steady_vs_first_speedup": calls[0] / steady}


def _obs_overhead() -> Dict[str, float]:
    """Probe-on vs probe-off cost of the 10k-request serving run,
    interleaved best-of-3.  ``sampled`` uses the default bundle sampling
    (``sample_every=64``); acceptance is < 10% overhead there (asserted
    by ``benchmarks/obs_smoke.py`` in CI)."""
    import gc

    from repro.obs import Probe
    from repro.serve_sim import ContinuousBatchingScheduler, ServingSimulator

    cost = _serve_cost()
    walls = {"off": float("inf"), "sampled": float("inf"),
             "full": float("inf")}
    for _ in range(3):
        for label, factory in (("off", lambda: None),
                               ("sampled", lambda: Probe(sample_every=64)),
                               ("full", lambda: Probe())):
            gc.collect()
            t0 = time.perf_counter()
            ServingSimulator(cost, ContinuousBatchingScheduler, _traffic(),
                             replicas=4, slots=8, probe=factory()).run()
            walls[label] = min(walls[label], time.perf_counter() - t0)
    return {
        "off_wall_seconds": walls["off"],
        "sampled_wall_seconds": walls["sampled"],
        "full_wall_seconds": walls["full"],
        "sampled_overhead_pct":
            (walls["sampled"] / walls["off"] - 1.0) * 100.0,
        "full_overhead_pct": (walls["full"] / walls["off"] - 1.0) * 100.0,
    }


def _median_merge(docs: List[Dict]) -> Dict:
    """Element-wise median across identically-shaped metric dicts."""
    out: Dict = {}
    for key, v in docs[0].items():
        if isinstance(v, dict):
            out[key] = _median_merge([d[key] for d in docs])
        elif isinstance(v, (int, float)):
            out[key] = statistics.median(d[key] for d in docs)
        else:
            out[key] = v
    return out


def collect(trials: int = 1) -> Dict:
    """One full suite pass — or, with ``trials > 1``, the per-metric
    median over that many passes (robust to a transiently loaded
    machine; see the module docstring on the PR 4 recording)."""
    from benchmarks import bench_engine

    def once() -> Dict:
        return {
            "engine_fifo_events_per_sec": bench_engine.fifo_events_per_sec(),
            "engine_shared_tasks_per_sec":
                bench_engine.shared_tasks_per_sec(),
            "engine_dynamic_injection_events_per_sec":
                bench_engine.dynamic_events_per_sec(),
            "what_if_points_per_sec": _what_if_points_per_sec(),
            "serve_sim_10k": _serve_sim_10k(),
            "serve_sim_10k_taskgraph": _serve_sim_10k_taskgraph(),
            "serve_sim_10k_speculative": _serve_sim_10k_speculative(),
            "serve_sim_10k_taskgraph_speculative":
                _serve_sim_10k_taskgraph_speculative(),
            "serve_sim_10k_chaos": _serve_sim_10k_chaos(),
            "cluster_1m_chaos": _cluster_1m_chaos(),
            "monte_carlo": _monte_carlo(),
            "persistent_pool": _persistent_pool(),
            "obs_overhead": _obs_overhead(),
        }

    if trials <= 1:
        return once()
    return _median_merge([once() for _ in range(trials)])


def _speedups(base: Dict, cur: Dict) -> Dict:
    """Per-metric current/baseline ratios; keys measured in seconds
    invert (baseline/current) so that > 1 always means faster."""
    out: Dict = {}
    for key, bval in base.items():
        if key not in cur:
            continue
        cval = cur[key]
        if isinstance(bval, dict):
            sub = {}
            for k, v in bval.items():
                if k not in cval or not v:
                    sub[k] = None
                elif k.endswith("seconds"):
                    sub[k] = round(v / cval[k], 2)
                else:
                    sub[k] = round(cval[k] / v, 2)
            out[key] = sub
        elif bval:
            out[key] = round(cval / bval, 2)
    return out


def write(path: str = "BENCH_pr10.json", trials: int = 1) -> Dict:
    current = collect(trials=trials)
    doc = {
        "pr": 10,
        "description": "Resilient cluster serving: health-checked "
                       "routing tier over heterogeneous replica pools "
                       "with failover, hedging, circuit breakers and "
                       "fault-aware autoscaling",
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "trials": trials,
        "note": "baseline_pr9 is a different-day recording on shared "
                "hardware; cross-recording ratios carry ~10-15% machine "
                "variance (verified by interleaving HEAD and PR 10 "
                "working trees on one machine: identical within noise). "
                "Regression gating uses the same-run paired floors in "
                "perf_smoke.py / cluster_smoke.py, not this file.",
        "baseline_pr9": BASELINE_PR9,
        "current": current,
        "speedup_vs_pr9": _speedups(BASELINE_PR9, current),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    return doc


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    argv = sys.argv[1:]
    trials = 1
    if "--trials" in argv:
        i = argv.index("--trials")
        trials = int(argv[i + 1])
        del argv[i:i + 2]
    out = write(argv[0] if argv else "BENCH_pr10.json", trials=trials)
    print(json.dumps({"speedup_vs_pr9": out["speedup_vs_pr9"],
                      "chaos": out["current"]["serve_sim_10k_chaos"],
                      "cluster": out["current"]["cluster_1m_chaos"],
                      }, indent=2))
