"""Serving-simulator throughput + scheduler comparison.

Measures the virtual serving stack at the scale the ROADMAP asks about:

  * sim speed — wall seconds (and simulated requests per wall second) for
    10k requests through continuous batching (acceptance: < 10 s on CPU);
  * task-graph mode — the same 10k requests with *full task-graph
    fidelity* (chunked phase graphs + KV writes, one ``TemplateLane``
    per replica);
  * speculative leap — 10k requests under a scheduler that declares only
    the ``decode_stable`` contract, so every decode fusion takes the
    snapshot/rollback path;
  * graph-mode speculative leap — the same decode_stable-only scheduler
    in full task-graph mode: each leap books
    one ``TemplateLane`` burst of per-step template instances and rolls
    back by truncating the burst at a snapshot boundary;
  * Monte-Carlo seed batch — 16 seeds x 10k requests in one
    ``MonteCarloServingSimulator`` call on the fused continuous-batching
    fast path, reporting cross-seed mean and 95% CI for p99 TTFT;
  * scheduler tails — p99 TTFT of continuous vs static batching under the
    same Poisson traffic (continuous batching should dominate);
  * cost-model derivation — seconds to fit a per-request cost model from
    compiled graphs, and the re-annotation fast path for a chip variant.
"""
from __future__ import annotations

import time
from typing import List, Tuple

from repro.core.avsm.model import annotate_system
from repro.core.config import get_arch
from repro.core.hw import SystemDescription, tpu_v5e_chip
from repro.core.taskgraph.builders import ShardPlan
from repro.serve_sim import (ContinuousBatchingScheduler, LengthDist,
                             MonteCarloServingSimulator,
                             ServingCostModelBuilder, ServingSimulator,
                             StaticBatchScheduler, poisson_workload,
                             poisson_workload_batch, simulate_serving)


class SpeculativeContinuousScheduler(ContinuousBatchingScheduler):
    """Continuous batching declaring only the speculative contract
    (``decode_stable`` without ``steady_decode``): every decode leap
    takes the snapshot/rollback path — the non-``steady_decode`` case
    the speculative leap opened up."""

    name = "continuous_speculative"
    steady_decode = False


def run() -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    cfg = get_arch("qwen1.5-0.5b").model
    base = SystemDescription(name="v5e_chip", chip=tpu_v5e_chip(), torus=())

    t0 = time.perf_counter()
    builder = ServingCostModelBuilder(cfg, shard=ShardPlan(data=1, model=1))
    cost = builder.model_for(base)
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    builder.model_for(annotate_system(base, mem_bandwidth=1638e9))
    t_refit = time.perf_counter() - t0
    rows.append(("serve_cost_fit", t_fit * 1e6,
                 f"variant_via_reannotate={t_refit * 1e6:.0f}us "
                 f"speedup={t_fit / max(t_refit, 1e-9):.0f}x"))

    def traffic(n, rate=120.0, seed=0):
        return poisson_workload(rate, n,
                                prompt=LengthDist(mean=512, cv=0.6),
                                output=LengthDist(mean=96, cv=0.5), seed=seed)

    t0 = time.perf_counter()
    rep = simulate_serving(cost, ContinuousBatchingScheduler, traffic(10_000),
                           replicas=4, slots=8)
    wall = time.perf_counter() - t0
    rows.append(("serve_sim_10k", wall * 1e6,
                 f"{rep.n_requests} reqs, {rep.output_tokens} toks, "
                 f"{rep.n_requests / wall:.0f} req/wall-s "
                 f"(accept: wall<10s)"))

    # full task-graph mode, best-of-2
    wall_g = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        g = ServingSimulator(cost, ContinuousBatchingScheduler,
                             traffic(10_000), replicas=4, slots=8,
                             phase_tasks=4).run()
        wall_g = min(wall_g, time.perf_counter() - t0)
    rows.append(("serve_sim_10k_taskgraph", wall_g * 1e6,
                 f"{g.n_requests} reqs, "
                 f"{g.n_requests / wall_g:.0f} req/wall-s "
                 f"({4 * 2} tasks/phase)"))

    # speculative decode leap: decode_stable-only scheduler, rollbacks on
    t0 = time.perf_counter()
    spec = simulate_serving(cost, SpeculativeContinuousScheduler,
                            traffic(10_000), replicas=4, slots=8)
    wall_spec = time.perf_counter() - t0
    rows.append(("serve_sim_10k_speculative", wall_spec * 1e6,
                 f"{spec.n_requests} reqs, "
                 f"{spec.n_requests / wall_spec:.0f} req/wall-s "
                 f"(decode_stable-only leap w/ rollback)"))

    # graph-mode speculative leap: full task-graph fidelity, leaps booked
    # as TemplateLane bursts with snapshot/rollback
    t0 = time.perf_counter()
    gspec = ServingSimulator(cost, SpeculativeContinuousScheduler,
                             traffic(10_000), replicas=4, slots=8,
                             phase_tasks=4).run()
    wall_gspec = time.perf_counter() - t0
    rows.append(("serve_sim_10k_taskgraph_speculative", wall_gspec * 1e6,
                 f"{gspec.n_requests} reqs, "
                 f"{gspec.n_requests / wall_gspec:.0f} req/wall-s "
                 f"(burst leap w/ rollback, {4 * 2} tasks/phase)"))

    # seed-batched Monte-Carlo: 16 seeds through the fused fast path
    batch = poisson_workload_batch(300.0, 10_000,
                                   prompt=LengthDist(mean=512, cv=0.6),
                                   output=LengthDist(mean=96, cv=0.5),
                                   seeds=16)
    t0 = time.perf_counter()
    mc = MonteCarloServingSimulator(cost, ContinuousBatchingScheduler,
                                    batch, replicas=4, slots=32).run()
    wall_mc = time.perf_counter() - t0
    s = mc.stat("ttft_p99")
    rows.append(("serve_sim_mc_16x10k", wall_mc * 1e6,
                 f"{mc.n_requests / wall_mc:.0f} "
                 f"seed-req/wall-s, ttft_p99={s.mean * 1e3:.2f}ms "
                 f"ci95=[{s.ci_lo * 1e3:.2f}, {s.ci_hi * 1e3:.2f}]ms"))

    cont = simulate_serving(cost, ContinuousBatchingScheduler,
                            traffic(2000, rate=60.0), replicas=4, slots=8)
    stat = simulate_serving(cost, lambda: StaticBatchScheduler(8, 0.25),
                            traffic(2000, rate=60.0), replicas=4, slots=8)
    rows.append(("serve_sched_p99_ttft", cont.ttft.p99 * 1e6,
                 f"static={stat.ttft.p99 * 1e6:.0f}us "
                 f"continuous_wins={cont.ttft.p99 <= stat.ttft.p99}"))
    return rows
