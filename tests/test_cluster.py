"""Resilient cluster serving (PR 10): health-checked routing tier with
failover, hedging, circuit breakers and fault-aware autoscaling.

The load-bearing contracts:

* **golden parity** — a 1-pool cluster behind :class:`PassThroughRouter`
  reproduces the standalone :class:`ServingSimulator` bit-exactly in
  every mode (express lane, graph mode per step, graph mode with
  speculative leaps), with and without faults: the routing tier is pure
  bookkeeping on that path.
* **leap parity** — a multi-pool graph-mode cluster with the full
  resilience stack (health checks, breakers, hedging, failover) gives
  the same counts, routes and tokens with speculative decode leaps as
  run per step, and the same latencies to round-off.
* **determinism** — seeded cluster scenarios (including Monte-Carlo
  sweeps) replay bit-identically across runs.
"""
import math

import pytest
from _hypothesis_compat import given, settings, st

from repro.serve_sim import (SLO, AutoscalerPolicy, CircuitBreaker,
                             CircuitBreakerPolicy, ClusterCapacityPlanner,
                             ClusterSimulator, ContinuousBatchingScheduler,
                             FailureModel, HealthCheckPolicy, HedgePolicy,
                             LeastLoadedRouter, MonteCarloClusterSimulator,
                             PassThroughRouter, ReplicaPool, RetryPolicy,
                             RoundRobinRouter, ServingCostModel,
                             ServingSimulator, StickyRouter, WeightedRouter,
                             diurnal_workload, diurnal_workload_batch,
                             make_router, poisson_workload,
                             poisson_workload_batch, simulate_cluster,
                             trace_workload)

FAST = ServingCostModel(name="fastchip", prefill_fixed=0.003,
                        prefill_per_token=1.5e-5, decode_fixed=0.0015,
                        decode_per_token=8e-6, decode_per_ctx_token=1.5e-8)
SLOW = ServingCostModel(name="slowchip", prefill_fixed=0.005,
                        prefill_per_token=2.5e-5, decode_fixed=0.0025,
                        decode_per_token=1.2e-5, decode_per_ctx_token=2.5e-8)

CHURN = FailureModel(mtbf=6.0, mttr=1.5, seed=3, horizon=30.0)


def _stats(s):
    return (s.p50, s.p95, s.p99, s.mean, s.n)


def _report_fields(r):
    return {
        "n_requests": r.n_requests, "duration": r.duration,
        "output_tokens": r.output_tokens, "replica_util": r.replica_util,
        "n_offered": r.n_offered, "n_failures": r.n_failures,
        "n_retries": r.n_retries, "n_abandoned": r.n_abandoned,
        "ttft": _stats(r.ttft), "tpot": _stats(r.tpot),
        "e2e": _stats(r.e2e), "qd": _stats(r.queue_delay),
    }


class PerStepContinuous(ContinuousBatchingScheduler):
    """Continuous batching without the speculative-leap contract: a batch
    with a free slot decodes step by step."""

    decode_stable = False


def _cluster_fields(r):
    return dict(_report_fields(r), availability=r.availability,
                n_failovers=r.n_failovers,
                hedges_issued=r.hedges_issued, hedges_won=r.hedges_won,
                hedge_waste_tokens=r.hedge_waste_tokens,
                n_lost=dict(r.n_lost), n_routed=dict(r.n_routed),
                breaker_trips=dict(r.breaker_trips),
                fleet_availability=r.fleet_availability)


# ---------------------------------------------------------------------------
# golden parity: 1-pool pass-through cluster == standalone simulator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["express", "graph_per_step", "graph"])
@pytest.mark.parametrize("faulty", [False, True])
def test_one_pool_passthrough_matches_standalone(mode, faulty):
    kw = dict(replicas=4, slots=4)
    if faulty:
        kw.update(failures=CHURN, retry=RetryPolicy())
    phase_tasks = 0 if mode == "express" else 2
    sched = (PerStepContinuous if mode == "graph_per_step"
             else ContinuousBatchingScheduler)

    def wl():
        return poisson_workload(40.0, 400, seed=7)

    solo = ServingSimulator(FAST, sched, wl(),
                            phase_tasks=phase_tasks, **kw).run()
    pool = ReplicaPool("only", FAST, kw["replicas"], slots=kw["slots"],
                       scheduler=sched, failures=kw.get("failures"),
                       retry=kw.get("retry"))
    clus = ClusterSimulator([pool], wl(), PassThroughRouter(),
                            phase_tasks=phase_tasks).run()
    assert _report_fields(solo) == _report_fields(clus)
    # the pool's own sub-report agrees with the aggregate too
    assert _report_fields(solo) == _report_fields(clus.pools["only"])
    # ServingReport.availability is fleet uptime; the cluster exposes it
    # as fleet_availability and reserves .availability for request success
    assert clus.fleet_availability == solo.availability
    assert clus.pools["only"].availability == solo.availability
    assert clus.availability == clus.n_requests / clus.n_offered
    assert clus.n_failovers == 0 and clus.hedges_issued == 0
    assert clus.n_lost_total == 0


def test_one_pool_parity_is_bit_exact_on_fused_metrics():
    wl = poisson_workload(60.0, 800, seed=11)
    solo = ServingSimulator(FAST, ContinuousBatchingScheduler,
                            poisson_workload(60.0, 800, seed=11),
                            replicas=8, slots=8, failures=CHURN,
                            retry=RetryPolicy()).run()
    clus = simulate_cluster(
        [ReplicaPool("p", FAST, 8, slots=8, failures=CHURN,
                     retry=RetryPolicy())], wl)
    assert solo.duration == clus.duration
    assert _stats(solo.e2e) == _stats(clus.e2e)
    assert solo.replica_util == clus.replica_util


# ---------------------------------------------------------------------------
# leap parity: full resilience stack, speculative leaps vs per step
# ---------------------------------------------------------------------------


def _chaos_pools(n=3, scheduler=ContinuousBatchingScheduler):
    return [
        ReplicaPool("zone-a", FAST, n, slots=4, scheduler=scheduler,
                    failures=FailureModel(mtbf=8.0, mttr=2.0, seed=11,
                                          horizon=40.0),
                    retry=RetryPolicy()),
        ReplicaPool("zone-b", SLOW, n, slots=4, scheduler=scheduler,
                    failures=FailureModel(mtbf=10.0, mttr=2.5, seed=12,
                                          horizon=40.0),
                    retry=RetryPolicy()),
        ReplicaPool("zone-c", FAST, n, slots=4, scheduler=scheduler,
                    failures=FailureModel(mtbf=9.0, mttr=2.0, seed=13,
                                          horizon=40.0),
                    retry=RetryPolicy()),
    ]


def _chaos_run(scheduler=ContinuousBatchingScheduler, phase_tasks=2):
    return ClusterSimulator(
        _chaos_pools(scheduler=scheduler),
        poisson_workload(60.0, 1200, seed=5),
        RoundRobinRouter(retry_budget=4), phase_tasks=phase_tasks,
        health=HealthCheckPolicy(interval=0.5),
        hedge=HedgePolicy(delay=0.8, max_fraction=0.1),
        breaker=CircuitBreakerPolicy(error_threshold=4, window=5.0,
                                     cooldown=5.0)).run()


def _assert_runs_agree(a, b):
    """Speculative leaps vs per step: every count, route and token is
    bit-exact; float latencies agree to within accumulation-order ULPs
    (a leap sums its steps in one pass, the per-step run step by step)."""
    fa, fb = _cluster_fields(a), _cluster_fields(b)
    for k in ("n_requests", "n_offered", "output_tokens", "n_failures",
              "n_retries", "n_abandoned", "n_failovers", "hedges_issued",
              "hedges_won", "hedge_waste_tokens", "n_lost", "n_routed",
              "breaker_trips"):
        assert fa[k] == fb[k], k
    for k in ("duration", "availability", "fleet_availability",
              "replica_util"):
        assert fa[k] == pytest.approx(fb[k], rel=1e-12), k
    for k in ("ttft", "tpot", "e2e", "qd"):
        assert fa[k] == pytest.approx(fb[k], rel=1e-9), k


def test_chaos_cluster_graph_leap_matches_per_step():
    a, b = _chaos_run(), _chaos_run(PerStepContinuous)
    _assert_runs_agree(a, b)
    for name in ("zone-a", "zone-b", "zone-c"):
        ra, rb = a.pools[name], b.pools[name]
        for k in ("n_requests", "n_offered", "output_tokens", "n_failures",
                  "n_retries", "n_abandoned"):
            assert getattr(ra, k) == getattr(rb, k), (name, k)
        assert _stats(ra.e2e) == pytest.approx(_stats(rb.e2e), rel=1e-9)


def test_chaos_cluster_seeded_replay_is_bit_identical():
    a, b = _chaos_run(), _chaos_run()
    assert _cluster_fields(a) == _cluster_fields(b)


def test_chaos_cluster_exercises_the_resilience_machinery():
    r = _chaos_run()
    assert r.n_requests == r.n_offered == 1200     # nothing lost end-to-end
    assert r.n_failures > 0 and r.n_failovers > 0
    assert r.hedges_issued > 0 and r.hedges_won > 0
    assert r.hedges_won <= r.hedges_issued
    assert r.hedges_issued <= 0.1 * r.n_offered + 1     # budget respected
    assert sum(r.breaker_trips.values()) > 0
    assert sum(r.n_routed.values()) == r.n_offered
    assert 0.0 < r.fleet_availability < 1.0
    assert r.availability == 1.0
    # accounting identity at cluster level
    assert r.n_offered == r.n_requests + r.n_abandoned + r.n_shed \
        + r.n_lost_total
    s = r.summary()
    assert "3 pools" in s and "failovers" in s and "hedges" in s


# ---------------------------------------------------------------------------
# router policies
# ---------------------------------------------------------------------------


class _FakeCluster:
    def __init__(self, loads, caps=None, weights=None):
        self._loads, self._caps = loads, caps or [1.0] * len(loads)
        self._weights = weights or [1.0] * len(loads)

    def pool_load(self, i):
        return self._loads[i]

    def pool_capacity(self, i):
        return self._caps[i]

    def pool_weight(self, i):
        return self._weights[i]


def _req(rid=0, user=-1):
    from repro.serve_sim import Request
    return Request(rid=rid, t_arrive=0.0, prompt_tokens=8, output_tokens=4,
                   user=user)


def test_round_robin_cycles_over_routable_set():
    r = RoundRobinRouter()
    picks = [r.pick([0, 1, 2], None, _req(i)) for i in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]
    # a pool leaving rotation shrinks the cycle without resetting it
    assert [r.pick([0, 2], None, _req()) for _ in range(4)] == [0, 2, 0, 2]


def test_least_loaded_normalizes_by_healthy_capacity():
    c = _FakeCluster(loads=[10.0, 10.0, 3.0], caps=[40.0, 8.0, 4.0])
    assert LeastLoadedRouter().pick([0, 1, 2], c, _req()) == 0   # 0.25 load
    c = _FakeCluster(loads=[5.0, 0.0], caps=[10.0, 10.0])
    assert LeastLoadedRouter().pick([0, 1], c, _req()) == 1


def test_weighted_router_matches_weight_proportions_smoothly():
    c = _FakeCluster(loads=[0, 0, 0], weights=[3.0, 1.0, 1.0])
    r = WeightedRouter()
    picks = [r.pick([0, 1, 2], c, _req()) for _ in range(50)]
    assert picks.count(0) == 30 and picks.count(1) == 10
    # smooth: never more than two consecutive picks of the heavy pool
    runs = max(len(list(g)) for g in
               "".join(map(str, picks)).replace("1", " ").replace("2", " ")
               .split())
    assert runs <= 2


def test_sticky_router_is_stable_per_user_and_remaps_minimally():
    r = StickyRouter()
    c = None
    full = {u: r.pick([0, 1, 2], c, _req(rid=u, user=u)) for u in range(64)}
    assert full == {u: r.pick([0, 1, 2], c, _req(rid=u, user=u))
                    for u in range(64)}
    assert len(set(full.values())) == 3           # all pools get sessions
    # anonymous requests fall back to rid hashing, still deterministic
    assert (r.pick([0, 1], c, _req(rid=9)) ==
            r.pick([0, 1], c, _req(rid=9)))


def test_router_registry_and_validation():
    assert isinstance(make_router("weighted"), WeightedRouter)
    assert make_router("round_robin", retry_budget=2).retry_budget == 2
    with pytest.raises(ValueError, match="unknown router"):
        make_router("nope")
    with pytest.raises(ValueError):
        RoundRobinRouter(retry_budget=-1)


# ---------------------------------------------------------------------------
# health checks: detection lag, hysteresis, rotation accounting
# ---------------------------------------------------------------------------


def test_health_checks_detect_outage_with_lag_and_shift_traffic():
    # zone-a is hard-down on [1, 12); health checks every 0.25 s with
    # unhealthy_after=2 detect it by t=1.5 and route around it.
    down = FailureModel(mtbf=1e6, mttr=1e5, seed=0, horizon=1.0)
    pools = [ReplicaPool("a", FAST, 2, slots=4, failures=down,
                         retry=RetryPolicy(max_attempts=6)),
             ReplicaPool("b", FAST, 2, slots=4)]
    explicit = [ReplicaPool("a", FAST, 2, slots=4,
                            failures=[__import__("repro.serve_sim",
                                                 fromlist=["ReplicaFault"])
                                      .ReplicaFault(r, 1.0, 12.0)
                                      for r in range(2)],
                            retry=RetryPolicy(max_attempts=6)),
                pools[1]]
    r = ClusterSimulator(explicit, poisson_workload(30.0, 450, seed=1),
                         RoundRobinRouter(),
                         health=HealthCheckPolicy(interval=0.25,
                                                  unhealthy_after=2,
                                                  healthy_after=2)).run()
    # out-of-rotation accumulates replica-seconds: two replicas out for
    # the ~11 s outage (detection lag trims the front, hysteresis pads
    # the back) land near 2 x 11.5
    assert 16.0 < r.time_out_of_rotation["a"] < 26.0
    assert r.time_out_of_rotation["b"] == 0.0
    # while a was out, b took everything: a's share is well under half
    assert r.n_routed["a"] < r.n_routed["b"]
    assert r.availability == 1.0                   # failover saved them all


def test_health_max_slow_factor_pulls_browned_out_replicas():
    slow = FailureModel(mtbf=3.0, mttr=2.0, mode="slow", slow_factor=8.0,
                        seed=4, horizon=20.0)
    r = ClusterSimulator(
        [ReplicaPool("s", SLOW, 3, slots=4, failures=slow),
         ReplicaPool("ok", FAST, 3, slots=4)],
        poisson_workload(40.0, 600, seed=2), LeastLoadedRouter(),
        health=HealthCheckPolicy(interval=0.5, max_slow_factor=4.0)).run()
    assert r.time_out_of_rotation["s"] > 0.0
    assert r.availability == 1.0                   # slow mode cancels nothing


# ---------------------------------------------------------------------------
# circuit breaker lifecycle
# ---------------------------------------------------------------------------


def test_breaker_trips_half_opens_and_closes():
    b = CircuitBreaker(CircuitBreakerPolicy(error_threshold=3, window=5.0,
                                            cooldown=10.0,
                                            half_open_probes=1))
    for t in (0.0, 1.0):
        b.record_error(t)
    assert b.state == b.CLOSED and b.allow(1.5)
    b.record_error(2.0)
    assert b.state == b.OPEN and b.n_trips == 1
    assert not b.allow(5.0)                        # still cooling down
    assert b.allow(12.0)                           # cooldown over: half-open
    assert b.state == b.HALF_OPEN
    b.on_route(12.0)
    assert not b.allow(12.5)                       # probe budget consumed
    b.record_success(13.0)
    assert b.state == b.CLOSED and b.allow(13.5)
    assert b.time_open == pytest.approx(11.0)      # 2.0 -> 13.0


def test_breaker_half_open_failure_reopens():
    b = CircuitBreaker(CircuitBreakerPolicy(error_threshold=1, window=5.0,
                                            cooldown=4.0))
    b.record_error(0.0)
    assert b.state == b.OPEN
    assert b.allow(4.5)                            # half-open probe
    b.record_error(5.0)
    assert b.state == b.OPEN and b.n_trips == 2
    assert not b.allow(6.0)
    b.finalize(9.0)
    # open [0, 5) + re-open [5, 9] = 9 s of open time in total
    assert b.time_open == pytest.approx(9.0)


def test_breaker_window_expires_old_errors():
    b = CircuitBreaker(CircuitBreakerPolicy(error_threshold=3, window=2.0,
                                            cooldown=1.0))
    b.record_error(0.0)
    b.record_error(0.5)
    b.record_error(5.0)                            # first two aged out
    assert b.state == b.CLOSED


# ---------------------------------------------------------------------------
# failover and the router-level retry budget
# ---------------------------------------------------------------------------


def test_retry_budget_exhaustion_counts_lost_requests():
    # a flapping pool keeps admitting and crash-cancelling work, so
    # pool-level retries fire repeatedly; retry_budget=0 turns the very
    # first router re-route into a loss.
    flap = FailureModel(mtbf=0.4, mttr=0.3, seed=9, horizon=30.0)
    r = ClusterSimulator(
        [ReplicaPool("flappy", FAST, 2, slots=4, failures=flap,
                     retry=RetryPolicy(max_attempts=10, backoff=0.05))],
        poisson_workload(20.0, 120, seed=3),
        RoundRobinRouter(retry_budget=0)).run()
    assert r.n_lost.get("budget", 0) > 0
    assert r.n_offered == r.n_requests + r.n_abandoned + r.n_shed \
        + r.n_lost_total
    # lost requests count against availability
    assert r.availability < 1.0


def test_failover_prefers_a_different_pool():
    from repro.serve_sim import ReplicaFault
    faults = [ReplicaFault(r, 0.5, 25.0) for r in range(2)]
    r = ClusterSimulator(
        [ReplicaPool("flaky", FAST, 2, slots=4, failures=faults,
                     retry=RetryPolicy(max_attempts=6)),
         ReplicaPool("solid", FAST, 2, slots=4)],
        poisson_workload(25.0, 300, seed=6), RoundRobinRouter()).run()
    assert r.n_failovers > 0
    assert r.availability == 1.0
    # every crash-lost request ended up served by the solid pool
    assert r.pools["solid"].n_requests > 150


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------


def test_hedging_requires_two_routable_pools():
    r = simulate_cluster(
        [ReplicaPool("solo", FAST, 2, slots=4)],
        poisson_workload(30.0, 200, seed=1),
        hedge=HedgePolicy(delay=0.01, max_fraction=1.0))
    assert r.hedges_issued == 0


def test_hedging_budget_and_waste_accounting():
    r = simulate_cluster(
        [ReplicaPool("a", FAST, 2, slots=4),
         ReplicaPool("b", SLOW, 2, slots=4)],
        poisson_workload(50.0, 500, seed=8),
        router=RoundRobinRouter(),
        hedge=HedgePolicy(delay=0.3, max_fraction=0.04))
    assert 0 < r.hedges_issued <= 0.04 * r.n_offered + 1
    assert r.hedges_won <= r.hedges_issued
    if r.hedges_won:
        assert r.hedge_waste_tokens >= 0
    assert r.n_requests == r.n_offered             # hedges never double-count


def test_hedge_delay_tracker_follows_the_p99():
    from repro.serve_sim.router import HedgeDelayTracker
    t = HedgeDelayTracker(HedgePolicy(quantile=0.5, min_samples=4,
                                      refresh_every=4, window=64))
    assert t.delay == math.inf                     # warm-up: disabled
    for v in (1.0, 2.0, 3.0, 4.0):
        t.observe(v)
    assert t.delay == 3.0                          # median of 4 samples
    fixed = HedgeDelayTracker(HedgePolicy(delay=0.25))
    fixed.observe(99.0)
    assert fixed.delay == 0.25                     # fixed delay never moves


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------


def test_autoscaler_scales_up_after_lag_and_drains_when_idle():
    # front-loaded burst then silence: orders fire early, activate after
    # the lag, and the tail drains back toward min_replicas.
    rows = [(0.002 * i, 96, 48) for i in range(400)]
    r = ClusterSimulator(
        [ReplicaPool("p", FAST, 1, slots=4, max_replicas=5, cost_rate=1.0)],
        trace_workload(rows), PassThroughRouter(),
        autoscaler=AutoscalerPolicy(interval=0.5, up_threshold=1.0,
                                    down_threshold=0.05, scale_up_lag=2.0,
                                    step=2)).run()
    ups = [e for e in r.scale_events if e[2] == 1]
    downs = [e for e in r.scale_events if e[2] == -1]
    assert ups and downs
    # nothing activates before the boot lag has elapsed
    assert min(t for t, _, _ in ups) >= 2.0
    assert r.n_requests == 400
    # cost integrates enabled replica-seconds, so it must exceed the
    # 1-replica floor but stay under the always-5 ceiling
    assert r.duration < r.enabled_seconds["p"] < 5 * r.duration
    assert r.cost == pytest.approx(r.enabled_seconds["p"])


def test_autoscaler_respects_max_replicas_headroom():
    rows = [(0.001 * i, 128, 64) for i in range(300)]
    r = ClusterSimulator(
        [ReplicaPool("p", SLOW, 1, slots=2, max_replicas=3)],
        trace_workload(rows), PassThroughRouter(),
        autoscaler=AutoscalerPolicy(interval=0.25, up_threshold=0.5,
                                    down_threshold=0.01, scale_up_lag=0.5,
                                    step=4)).run()
    # never more than max_replicas enabled at once
    assert r.enabled_seconds["p"] <= 3 * r.duration + 1e-9
    assert r.n_requests == 300


def test_autoscaler_seeded_replay_is_deterministic():
    def run():
        return ClusterSimulator(
            [ReplicaPool("a", FAST, 2, slots=4, max_replicas=6),
             ReplicaPool("b", SLOW, 2, slots=4, max_replicas=6)],
            diurnal_workload(50.0, 800, period=30.0, seed=9),
            LeastLoadedRouter(),
            autoscaler=AutoscalerPolicy(interval=1.0, scale_up_lag=3.0)).run()
    a, b = run(), run()
    assert _cluster_fields(a) == _cluster_fields(b)
    assert a.scale_events == b.scale_events
    assert a.cost == b.cost


# ---------------------------------------------------------------------------
# diurnal workload
# ---------------------------------------------------------------------------


def test_diurnal_workload_scalar_vs_batch_bit_parity():
    wl = diurnal_workload(30.0, 200, period=60.0, amplitude=0.6, seed=5)
    batch = diurnal_workload_batch(30.0, 200, period=60.0, amplitude=0.6,
                                   seeds=(5,))
    solo = [(q.rid, q.t_arrive, q.prompt_tokens, q.output_tokens)
            for q in wl.initial()]
    fused = [(q.rid, q.t_arrive, q.prompt_tokens, q.output_tokens)
             for q in batch.workload(0).initial()]
    assert solo == fused


def test_diurnal_workload_modulates_arrival_rate():
    wl = diurnal_workload(50.0, 4000, period=100.0, amplitude=0.9, seed=0)
    ts = [q.t_arrive for q in wl.initial()]
    assert ts == sorted(ts)
    # peak quarter of the cycle vs trough quarter: heavily asymmetric
    peak = sum(1 for t in ts if (t % 100.0) < 50.0)
    trough = len(ts) - peak
    assert peak > 2 * trough


def test_diurnal_workload_validation():
    for kw in ({"rate_mean": 0.0}, {"amplitude": -0.1}, {"amplitude": 1.5},
               {"period": 0.0}):
        with pytest.raises(ValueError):
            diurnal_workload(**{"rate_mean": 10.0, "n_requests": 10, **kw})


# ---------------------------------------------------------------------------
# Monte-Carlo cluster sweeps
# ---------------------------------------------------------------------------


def test_mc_cluster_deterministic_and_seed_decorrelated():
    batch = poisson_workload_batch(50.0, 300, seeds=3)

    def run():
        return MonteCarloClusterSimulator(
            _chaos_pools(2), batch, RoundRobinRouter,
            health=HealthCheckPolicy(interval=0.5)).run()

    a, b = run(), run()
    assert a.seeds == b.seeds == (0, 1, 2)
    for ra, rb in zip(a.reports, b.reports):
        assert _cluster_fields(ra) == _cluster_fields(rb)
    # per-seed fault draws differ: durations are not all identical
    assert len({r.duration for r in a.reports}) > 1
    st_ = a.stat("availability")
    assert 0.0 <= st_.ci_lo <= st_.mean <= 1.0
    assert a.stat("cost").mean > 0
    assert "3 seeds" in a.summary()


def test_mc_cluster_rejects_manual_fault_seed():
    with pytest.raises(ValueError, match="fault_seed"):
        MonteCarloClusterSimulator(_chaos_pools(2),
                                   poisson_workload_batch(10.0, 50, seeds=2),
                                   fault_seed=1)


# ---------------------------------------------------------------------------
# capacity planning: per-pool sizing and N+k redundancy
# ---------------------------------------------------------------------------


def _planner(num_seeds=1, slo=None):
    return ClusterCapacityPlanner(
        pools_factory=lambda n: [
            ReplicaPool("a", FAST, n, slots=4, failures=CHURN,
                        retry=RetryPolicy()),
            ReplicaPool("b", FAST, n, slots=4)],
        workload_factory=lambda: (
            poisson_workload_batch(30.0, 250, seeds=num_seeds)
            if num_seeds > 1 else poisson_workload(30.0, 250, seed=0)),
        slo=slo or SLO(e2e_p99=20.0, availability=0.95),
        router_factory=RoundRobinRouter, num_seeds=num_seeds,
        health=HealthCheckPolicy(interval=0.5))


def test_cluster_planner_bisects_replicas_per_pool():
    plan = _planner().plan(lo=1, cap=8)
    assert plan.feasible
    assert plan.axis == "replicas_per_pool"
    assert 1 <= plan.value <= 8
    # minimality: one replica fewer (if legal) was probed infeasible
    if plan.value > 1:
        assert plan.value - 1 in plan.reports


def test_cluster_planner_redundancy_decision_with_ci():
    rp = _planner(num_seeds=3).plan_redundancy(base=1, extras=(0, 1, 2))
    assert set(rp.options) == {0, 1, 2}
    assert rp.feasible
    assert rp.choice == min(k for k, ok in rp.options.items() if ok)
    # monotone in k for an availability SLO under a fixed fault profile
    ks = sorted(rp.options)
    first_ok = next((k for k in ks if rp.options[k]), None)
    if first_ok is not None:
        assert all(rp.options[k] for k in ks if k >= first_ok)
    assert f"N+{rp.choice}" in str(rp)
    # CI-conservative availability backed the decision
    assert rp.reports[rp.choice].stat("availability").ci_lo >= 0.95


def test_cluster_planner_infeasible_redundancy_reports_miss():
    rp = _planner(slo=SLO(e2e_p99=1e-6)).plan_redundancy(base=1,
                                                         extras=(0,))
    assert not rp.feasible and rp.choice is None
    assert "MISS" in str(rp)


# ---------------------------------------------------------------------------
# validation and observability
# ---------------------------------------------------------------------------


def test_replica_pool_and_cluster_validation():
    with pytest.raises(ValueError):
        ReplicaPool("", FAST, 1)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 0)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 1, slots=0)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 1, weight=0.0)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 1, weight=math.nan)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 1, cost_rate=-1.0)
    with pytest.raises(ValueError):
        ReplicaPool("p", FAST, 4, max_replicas=2)
    wl = poisson_workload(5.0, 10)
    with pytest.raises(ValueError, match="unique"):
        ClusterSimulator([ReplicaPool("x", FAST, 1),
                          ReplicaPool("x", SLOW, 1)], wl)
    with pytest.raises(ValueError):
        ClusterSimulator([ReplicaPool("x", FAST, 1)], wl,
                         fault_seed=[1, 2])
    with pytest.raises(ValueError):
        ClusterSimulator([], wl)


def test_cluster_probe_namespaces_per_pool_and_router_series():
    from repro.obs import Probe
    p = Probe("cluster-run")
    _chaos = ClusterSimulator(
        _chaos_pools(2), poisson_workload(40.0, 300, seed=5),
        RoundRobinRouter(retry_budget=4), probe=p,
        health=HealthCheckPolicy(interval=0.5),
        hedge=HedgePolicy(delay=0.8, max_fraction=0.1)).run()
    series = p.all_series()
    for name in ("zone-a", "zone-b"):
        assert any(s.startswith(f"cluster/{name}/") for s in series)
        assert f"cluster/{name}/in_rotation" in series
    assert "cluster/router/failovers" in series
    assert "cluster/router/hedges" in series
    m = p.to_metrics()
    assert m["counters"]["cluster/router/failovers"] == _chaos.n_failovers
    assert m["counters"]["cluster/router/hedges"] == _chaos.hedges_issued


def test_probe_does_not_perturb_cluster_results():
    from repro.obs import Probe
    base = _chaos_run()
    p = Probe("parity")
    inst = ClusterSimulator(
        _chaos_pools(), poisson_workload(60.0, 1200, seed=5),
        RoundRobinRouter(retry_budget=4), phase_tasks=2,
        health=HealthCheckPolicy(interval=0.5),
        hedge=HedgePolicy(delay=0.8, max_fraction=0.1),
        breaker=CircuitBreakerPolicy(error_threshold=4, window=5.0,
                                     cooldown=5.0), probe=p).run()
    assert _cluster_fields(base) == _cluster_fields(inst)
    assert p.all_series()


# ---------------------------------------------------------------------------
# engine: every() periodic callbacks
# ---------------------------------------------------------------------------


def test_engine_every_runs_until_fn_returns_false():
    from repro.core.sim.engine import DynamicSimulator
    sim = DynamicSimulator()
    ticks = []
    sim.at(0.0, lambda: None)

    def tick():
        ticks.append(sim.now)
        return len(ticks) < 3

    sim.every(0.5, tick, start=0.25)
    sim.run()
    assert ticks == [0.25, 0.75, 1.25]


def test_engine_every_rejects_bad_interval():
    from repro.core.sim.engine import DynamicSimulator
    sim = DynamicSimulator()
    for bad in (0.0, -1.0, math.nan, math.inf):
        with pytest.raises(ValueError):
            sim.every(bad, lambda: False)


# ---------------------------------------------------------------------------
# property: 1-pool golden parity over arbitrary seeds
# ---------------------------------------------------------------------------


def _parity_at(seed: int) -> None:
    kw = dict(replicas=3, slots=4,
              failures=FailureModel(mtbf=4.0, mttr=1.0, seed=seed,
                                    horizon=20.0),
              retry=RetryPolicy())
    solo = ServingSimulator(FAST, ContinuousBatchingScheduler,
                            poisson_workload(25.0, 150, seed=seed),
                            **kw).run()
    clus = simulate_cluster(
        [ReplicaPool("p", FAST, 3, slots=4, failures=kw["failures"],
                     retry=kw["retry"])],
        poisson_workload(25.0, 150, seed=seed))
    assert _report_fields(solo) == _report_fields(clus)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_property_one_pool_parity_any_seed(seed):
    _parity_at(seed)


def test_sweep_one_pool_parity():
    """Deterministic fallback for the hypothesis property above."""
    for seed in (0, 17, 512):
        _parity_at(seed)
