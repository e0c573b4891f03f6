"""Resilient cluster serving: a routing tier over heterogeneous pools.

The ROADMAP's fleet-scale question — *which balancer policy, health-check
interval, hedging budget and redundancy level actually hold the
availability SLO under zone-correlated churn?* — is answered here the
way the paper answers hardware questions: on a virtual model, before any
cluster exists.  A :class:`ClusterSimulator` composes named
:class:`ReplicaPool`\\ s (each a :class:`~repro.serve_sim.simulator.
ServingSimulator` with its own chip-variant cost model, slot count,
scheduler and :class:`~repro.serve_sim.faults.FailureModel`) on **one**
shared DES engine, behind a pluggable
:class:`~repro.serve_sim.router.RouterPolicy`, and layers the
resilience machinery on top:

* **health checks** — periodic probes with hysteresis
  (:class:`~repro.serve_sim.router.HealthCheckPolicy`) drive replicas in
  and out of the routing rotation, so crashes are *detected* with
  realistic lag rather than omnisciently avoided;
* **failover** — a request cancelled by a replica crash re-enters
  through the router (PR 9's epoch-invalidation rollback + retry heap
  decide *when*; the router decides *where*), under a router-level
  ``retry_budget``;
* **hedging** — a request still unfinished after a p99-derived delay is
  duplicated to a second pool; first completion wins, the loser is
  cancelled at its next scheduler boundary (the same instants whether or
  not decode steps are fused, so leap-vs-per-step parity survives
  cancellation);
* **circuit breakers** — per-pool error-rate trips with half-open
  probing (:class:`~repro.serve_sim.router.CircuitBreakerPolicy`);
* **autoscaling** — a reactive
  :class:`~repro.serve_sim.router.AutoscalerPolicy` orders replicas
  (active after a scale-up lag) and drains them on low pressure, so
  N+1-vs-N+2 and policy trade-offs come out as availability/goodput/
  cost numbers in the :class:`ClusterReport`.

Parity contract (``tests/test_cluster.py``): a 1-pool cluster with
pass-through routing and no health checks reproduces the standalone
:class:`~repro.serve_sim.simulator.ServingSimulator` report bit-exactly
in express and graph mode — the cluster hooks are bookkeeping-only on
that path (no RNG draws, no extra heap events at decision points).

:class:`MonteCarloClusterSimulator` runs the cluster across a
seed-batched :class:`~repro.serve_sim.workload.RequestBatch` (per-seed
fault schedules decorrelated per pool) and reports cross-seed
:class:`~repro.serve_sim.monte_carlo.SeedStats`, which the
:class:`~repro.serve_sim.capacity.ClusterCapacityPlanner` consumes for
CI-conservative availability sizing.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.sim.engine import DynamicSimulator, SimResult
from repro.serve_sim.cost import ServingCostModel
from repro.serve_sim.faults import FailureModel, RetryPolicy
from repro.serve_sim.router import (AutoscalerPolicy, CircuitBreaker,
                                    CircuitBreakerPolicy, HealthCheckPolicy,
                                    HedgeDelayTracker, HedgePolicy,
                                    RouterPolicy, RoundRobinRouter)
from repro.serve_sim.scheduler import (BatchScheduler,
                                       ContinuousBatchingScheduler, InFlight)
from repro.serve_sim.simulator import (LaneStateArrays, LatencyStats,
                                       ServingReport, ServingSimulator)
from repro.serve_sim.workload import Request, RequestBatch, Workload

__all__ = [
    "ReplicaPool", "ClusterSimulator", "ClusterReport", "simulate_cluster",
    "MonteCarloClusterSimulator", "MonteCarloClusterReport",
]


@dataclass(frozen=True)
class ReplicaPool:
    """One homogeneous pool inside a heterogeneous cluster.

    A pool is a chip variant deployed as ``replicas`` identical serving
    replicas with ``slots`` batch slots each, its own scheduler policy
    and (optionally) its own fault profile — e.g. ``zone-a`` on the
    incumbent chip and ``zone-c`` on the faster annotated variant.

    ``weight`` feeds :class:`~repro.serve_sim.router.WeightedRouter`
    (default: capacity scaled by chip speed).  ``cost_rate`` is the
    pool's cost per replica-second (relative units) — the autoscaler's
    enabled-seconds integral times this rate is the pool's cost in the
    :class:`ClusterReport`.  ``max_replicas`` is autoscaler headroom:
    replicas beyond ``replicas`` exist but start drained.
    """

    name: str
    cost: ServingCostModel
    replicas: int
    slots: int = 8
    scheduler: Callable[[], BatchScheduler] = ContinuousBatchingScheduler
    failures: object = None
    retry: Optional[RetryPolicy] = None
    weight: Optional[float] = None
    cost_rate: float = 1.0
    max_replicas: Optional[int] = None

    def __post_init__(self):
        if not isinstance(self.name, str) or not self.name:
            raise ValueError("ReplicaPool.name must be a non-empty string")
        if not isinstance(self.replicas, int) or self.replicas < 1:
            raise ValueError(f"ReplicaPool.replicas must be an int >= 1, "
                             f"got {self.replicas!r}")
        if not isinstance(self.slots, int) or self.slots < 1:
            raise ValueError(f"ReplicaPool.slots must be an int >= 1, "
                             f"got {self.slots!r}")
        w = self.weight
        if w is not None and not (isinstance(w, (int, float))
                                  and math.isfinite(w) and w > 0):
            raise ValueError(f"ReplicaPool.weight must be finite and > 0, "
                             f"got {w!r}")
        cr = self.cost_rate
        if not (isinstance(cr, (int, float)) and math.isfinite(cr)
                and cr >= 0):
            raise ValueError(f"ReplicaPool.cost_rate must be finite and "
                             f">= 0, got {cr!r}")
        mr = self.max_replicas
        if mr is not None and (not isinstance(mr, int) or mr < self.replicas):
            raise ValueError("ReplicaPool.max_replicas must be an int >= "
                             f"replicas ({self.replicas}), got {mr!r}")


@dataclass
class ClusterReport:
    """Cluster-wide serving estimate: per-pool reports + routing metrics.

    ``availability`` here is *request-level* — completed / offered — the
    quantity an availability SLO constrains at the routing tier (a
    cluster can keep serving through replica churn; what users see is
    whether their request completed).  ``fleet_availability`` is the
    replica-seconds-up fraction the per-pool fault windows imply, for
    comparison against the single-pool notion.
    """

    workload: str
    router: str
    pools: Dict[str, ServingReport]
    replicas: int                       # total built replicas, all pools
    duration: float                     # shared-engine makespan, seconds
    n_offered: int                      # requests routed (excl. retries)
    n_requests: int                     # completed cluster-wide
    output_tokens: int
    ttft: LatencyStats
    tpot: LatencyStats
    e2e: LatencyStats
    queue_delay: LatencyStats
    replica_util: float
    availability: float                 # completed / offered
    fleet_availability: float           # replica-seconds up (fault windows)
    # ---- resilience / routing metrics -----------------------------------
    n_failures: int = 0
    n_retries: int = 0
    n_failovers: int = 0                # retries re-routed through the router
    retries_suppressed: int = 0         # retry fired while a twin still ran
    n_failopen: int = 0                 # routed with zero routable pools
    n_abandoned: int = 0
    n_shed: int = 0
    n_lost: Dict[str, int] = field(default_factory=dict)
    hedges_issued: int = 0
    hedges_won: int = 0                 # the duplicate finished first
    hedge_waste_tokens: int = 0         # tokens decoded by losing copies
    breaker_trips: Dict[str, int] = field(default_factory=dict)
    breaker_open_time: Dict[str, float] = field(default_factory=dict)
    time_out_of_rotation: Dict[str, float] = field(default_factory=dict)
    n_routed: Dict[str, int] = field(default_factory=dict)
    scale_events: List[Tuple] = field(default_factory=list)
    enabled_seconds: Dict[str, float] = field(default_factory=dict)
    cost: float = 0.0                   # sum_i enabled_seconds_i * rate_i
    events: List[Tuple] = field(default_factory=list)

    @property
    def throughput_rps(self) -> float:
        return self.n_requests / self.duration if self.duration > 0 else 0.0

    @property
    def throughput_tps(self) -> float:
        return self.output_tokens / self.duration if self.duration > 0 else 0.0

    @property
    def goodput_rps(self) -> float:
        return self.throughput_rps

    @property
    def attempt_rps(self) -> float:
        if self.duration <= 0:
            return 0.0
        return (self.n_requests + self.n_retries) / self.duration

    @property
    def abandonment_rate(self) -> float:
        """Fraction of offered requests the cluster never completed."""
        if self.n_offered <= 0:
            return 0.0
        return (self.n_offered - self.n_requests) / self.n_offered

    @property
    def n_lost_total(self) -> int:
        return sum(self.n_lost.values())

    def summary(self) -> str:
        shares = "+".join(f"{name}:{self.n_routed.get(name, 0)}"
                          for name in self.pools)
        s = (
            f"cluster[{self.router}|{self.workload}] "
            f"{len(self.pools)} pools / {self.replicas} replicas: "
            f"{self.n_requests}/{self.n_offered} reqs in "
            f"{self.duration:.1f}s ({self.throughput_rps:.2f} req/s, "
            f"util={self.replica_util:.1%}, "
            f"availability={self.availability:.4%})\n"
            f"  TTFT p50/p99 = {self.ttft.p50 * 1e3:.0f}/"
            f"{self.ttft.p99 * 1e3:.0f} ms   "
            f"E2E p99 = {self.e2e.p99:.2f} s   routed {shares}")
        if (self.n_failures or self.n_failovers or self.hedges_issued
                or self.n_lost or self.scale_events):
            trips = sum(self.breaker_trips.values())
            s += (
                f"\n  resilience: {self.n_failures} failures, "
                f"{self.n_failovers} failovers "
                f"({self.retries_suppressed} suppressed), "
                f"{self.hedges_issued} hedges ({self.hedges_won} won), "
                f"{trips} breaker trips, "
                f"{self.n_lost_total} lost {dict(self.n_lost)}, "
                f"{len(self.scale_events)} scale events, "
                f"cost={self.cost:.0f}")
        return s


class ClusterSimulator:
    """Routes one workload over heterogeneous replica pools on a shared
    DES engine.

    ``pools`` is a list of :class:`ReplicaPool` specs; ``router`` a
    :class:`~repro.serve_sim.router.RouterPolicy` (default round-robin).
    ``health`` / ``hedge`` / ``breaker`` / ``autoscaler`` switch on the
    corresponding machinery; all default off, and with exactly one pool,
    a pass-through router and everything off, the run is bit-identical
    to the standalone :class:`ServingSimulator` (the golden contract).

    ``fault_seed``: ``None`` or a scalar/tuple is forwarded verbatim to
    every pool (the parity configuration); a *list* supplies one
    override per pool (how the Monte-Carlo wrapper decorrelates pools
    per seed).
    """

    def __init__(self, pools: Sequence[ReplicaPool], workload: Workload,
                 router: Optional[RouterPolicy] = None, *,
                 health: Optional[HealthCheckPolicy] = None,
                 hedge: Optional[HedgePolicy] = None,
                 breaker: Optional[CircuitBreakerPolicy] = None,
                 autoscaler: Optional[AutoscalerPolicy] = None,
                 phase_tasks: int = 0,
                 probe=None,
                 record_events: bool = False,
                 fault_seed=None):
        pools = list(pools)
        if not pools:
            raise ValueError("need at least one ReplicaPool")
        names = [p.name for p in pools]
        if len(set(names)) != len(names):
            raise ValueError(f"pool names must be unique, got {names}")
        if isinstance(fault_seed, list) and len(fault_seed) != len(pools):
            raise ValueError(f"fault_seed list has {len(fault_seed)} "
                             f"entries for {len(pools)} pools")
        self.pools = pools
        self.workload = workload
        self.router = router if router is not None else RoundRobinRouter()
        self.health = health
        self.autoscaler = autoscaler
        self.record_events = record_events
        self.probe = probe
        P = self._n_pools = len(pools)

        # One engine for the whole cluster: pools share its event heap.
        self._sim = DynamicSimulator()

        try:
            expected = int(workload.n_requests)
        except Exception:
            expected = -1
        self._expected = expected if expected >= 0 else (1 << 62)

        # ---- pool runtimes ----------------------------------------------
        self._rts: List[ServingSimulator] = []
        for i, spec in enumerate(pools):
            n_built = spec.replicas
            if autoscaler is not None and spec.max_replicas is not None:
                n_built = spec.max_replicas
            fs = fault_seed[i] if isinstance(fault_seed, list) else fault_seed
            rt = ServingSimulator(
                spec.cost, spec.scheduler, workload,
                replicas=n_built, slots=spec.slots,
                record_events=record_events, phase_tasks=phase_tasks,
                probe=probe, failures=spec.failures,
                retry=spec.retry, fault_seed=fs, sim=self._sim,
                res_prefix=f"{spec.name}/", obs_ns=f"cluster/{spec.name}")
            if P > 1 and expected > 16 * P:
                # each pool serves only a share of the trace; shrink the
                # (grow-on-demand) per-pool metric columns accordingly
                rt.lane_state = LaneStateArrays(
                    capacity=expected // P + 64)
            # bind the cluster hooks (bookkeeping-only on the hot path)
            rt._route_hook = self._route_new
            rt._retry_hook = self._make_retry_hook(i)
            rt._abandon_hook = self._make_abandon_hook(i)
            rt._shed_hook = self._make_shed_hook(i)
            rt._finish_hook = self._make_finish_hook(i)
            if autoscaler is not None and n_built > spec.replicas:
                rt._enabled = [r < spec.replicas for r in range(n_built)]
            self._rts.append(rt)

        # ---- per-request routing state ----------------------------------
        n0 = min(self._expected, 1 << 20)
        n0 = max(n0, 16)
        self._completed = bytearray(n0)
        self._lost = bytearray(n0)
        self._hedged = bytearray(n0)
        self._live = [0] * n0
        self._fails = [0] * n0
        self._where = [-1] * n0
        self._pending_retry = [0] * n0
        self._copies: Dict[int, List[int]] = {}

        # ---- counters ----------------------------------------------------
        self.n_offered = 0
        self.n_completed = 0
        self._resolved = 0
        self.n_failovers = 0
        self.retries_suppressed = 0
        self.n_failopen = 0
        self.hedges_issued = 0
        self.hedges_won = 0
        self.hedge_waste_tokens = 0
        self.n_lost: Dict[str, int] = {}
        self.n_routed = [0] * P
        self.routing_events: List[Tuple] = []
        self._pending_routes = 0
        self._pending_retry_total = 0
        self._pending_hedges = 0

        # ---- health-check state -----------------------------------------
        if health is not None:
            self._in_rot = [[True] * len(rt.replicas) for rt in self._rts]
            self._h_bad = [[0] * len(rt.replicas) for rt in self._rts]
            self._h_good = [[0] * len(rt.replicas) for rt in self._rts]
            self._out_since = [[0.0] * len(rt.replicas) for rt in self._rts]
            self._rotation = [rt.n_enabled() for rt in self._rts]
        else:
            self._rotation = None
        self._t_out = [0.0] * P

        # ---- circuit breakers -------------------------------------------
        self._breakers = ([CircuitBreaker(breaker) for _ in pools]
                          if breaker is not None else None)

        # ---- hedging -----------------------------------------------------
        self._hedge = hedge
        self._hedge_tracker = (HedgeDelayTracker(hedge)
                               if hedge is not None else None)

        # ---- autoscaler / cost accounting -------------------------------
        self._pending_orders = [0] * P
        self._en_count = [rt.n_enabled() for rt in self._rts]
        self._en_seconds = [0.0] * P
        self._en_last = [0.0] * P
        self.scale_events: List[Tuple] = []

        # router fast path: with no rotation/breaker/scaling machinery,
        # every pool is always routable
        self._all_pools = list(range(P))
        self._static_routing = (health is None and breaker is None
                                and autoscaler is None)

        # default weighted-router weights: capacity scaled by chip speed
        self._weights: List[float] = []
        for spec in pools:
            w = spec.weight
            if w is None:
                try:
                    step = float(spec.cost.decode_step_time(1, 512))
                except Exception:
                    step = 1.0
                w = spec.replicas * spec.slots / max(step, 1e-12)
            self._weights.append(float(w))

        if probe is not None:
            self._p_rot = [probe.gauge(f"cluster/{spec.name}/in_rotation",
                                       unit="replicas") for spec in pools]
            self._p_en = [probe.gauge(f"cluster/{spec.name}/enabled",
                                      unit="replicas") for spec in pools]
            self._p_failover = probe.counter("cluster/router/failovers")
            self._p_hedges = probe.counter("cluster/router/hedges")
            self._p_lost = probe.counter("cluster/router/lost",
                                         unit="requests")

    def _ensure(self, rid: int) -> None:
        n = len(self._live)
        if rid < n:
            return
        grow = max(rid + 1 - n, n)
        self._completed.extend(b"\0" * grow)
        self._lost.extend(b"\0" * grow)
        self._hedged.extend(b"\0" * grow)
        self._live.extend([0] * grow)
        self._fails.extend([0] * grow)
        self._where.extend([-1] * grow)
        self._pending_retry.extend([0] * grow)

    # ---- router view of the cluster -------------------------------------

    def pool_load(self, i: int) -> float:
        """Queued + in-flight requests at pool ``i`` — what a balancer
        observes at its own edge (not the pool's internal fault state)."""
        rt = self._rts[i]
        return len(rt.pending) + sum(len(rep.active) for rep in rt.replicas)

    def pool_capacity(self, i: int) -> float:
        """Healthy capacity: in-rotation replicas times slots."""
        return self._rot_count(i) * self.pools[i].slots

    def pool_weight(self, i: int) -> float:
        return self._weights[i]

    def _rot_count(self, i: int) -> int:
        if self._rotation is not None:
            return self._rotation[i]
        return self._en_count[i]

    def _routable(self, now: float) -> List[int]:
        if self._static_routing:
            return self._all_pools
        out = []
        bks = self._breakers
        for i in range(self._n_pools):
            if self._rot_count(i) <= 0:
                continue
            if bks is not None and not bks[i].allow(now):
                continue
            out.append(i)
        if not out:
            # fail open: a router with nowhere to go still routes (the
            # alternative is silently dropping traffic); counted so the
            # report shows how often the cluster flew blind
            self.n_failopen += 1
            return self._all_pools
        return out

    def _pick(self, cands: List[int], req: Request, now: float) -> int:
        j = self.router.pick(cands, self, req)
        if self._breakers is not None:
            self._breakers[j].on_route(now)
        return j

    # ---- arrivals and routing -------------------------------------------

    def _route_new(self, req: Request) -> None:
        """Entry point for every first-attempt arrival (initial trace and
        closed-loop follow-ups re-entering via the pool route hook)."""
        self._pending_routes += 1
        self._sim.at(max(0.0, req.t_arrive),
                     lambda r=req: self._dispatch(r))

    def _dispatch(self, req: Request) -> None:
        now = self._sim.now
        self._pending_routes -= 1
        self.n_offered += 1
        rid = req.rid
        self._ensure(rid)
        j = self._pick(self._routable(now), req, now)
        self._live[rid] = 1
        self._where[rid] = j
        self.n_routed[j] += 1
        rt = self._rts[j]
        rt._n_offered += 1
        if self.record_events:
            self.routing_events.append(("route", rid, j))
        rt._arrive(req, now)
        hp = self._hedge
        if hp is not None and self._n_pools > 1:
            d = self._hedge_tracker.delay
            if d < math.inf:
                self._pending_hedges += 1
                self._sim.at(now + d, lambda r=req: self._maybe_hedge(r))

    def _maybe_hedge(self, req: Request) -> None:
        self._pending_hedges -= 1
        rid = req.rid
        # still on its first attempt, unfinished, and unhedged?  (a
        # request in retry limbo has live == 0; hedging it would race
        # the failover path for no benefit)
        if self._completed[rid] or self._hedged[rid] or self._live[rid] != 1:
            return
        hp = self._hedge
        if self.hedges_issued + 1 > hp.max_fraction * max(1, self.n_offered):
            return                      # hedging budget exhausted
        now = self._sim.now
        origin = self._where[rid]
        cands = [i for i in self._routable(now) if i != origin]
        if not cands:
            return
        j = self._pick(cands, req, now)
        self._hedged[rid] = 1
        self.hedges_issued += 1
        self._copies[rid] = [origin, j]
        self._live[rid] += 1
        if self.record_events:
            self.routing_events.append(("hedge", rid, origin, j))
        self._rts[j]._arrive(req, now)

    # ---- pool hook factories --------------------------------------------

    def _make_finish_hook(self, i: int):
        def on_finish(fl: InFlight, now: float) -> bool:
            rid = fl.req.rid
            if self._completed[rid]:
                # the losing hedge copy reached a scheduler boundary
                # after the winner finished: swallow it (no metrics row,
                # no closed-loop follow-up) and account the waste
                self._live[rid] -= 1
                self._rts[i]._cancelled_rids.discard(rid)
                self.hedge_waste_tokens += fl.generated
                return False
            self._completed[rid] = 1
            self._live[rid] -= 1
            self.n_completed += 1
            self._resolved += 1
            if self._breakers is not None:
                self._breakers[i].record_success(now)
            tr = self._hedge_tracker
            if tr is not None:
                tr.observe(now - fl.req.t_arrive)
            copies = self._copies.pop(rid, None)
            if copies is not None:
                other = copies[0] if copies[1] == i else copies[1]
                if i == copies[1]:
                    self.hedges_won += 1
                if self.record_events:
                    self.routing_events.append(("hedge_win", rid, i))
                if self._live[rid] > 0:
                    if self._rts[other].cancel_request(rid, now) == "queued":
                        self._live[rid] -= 1
            return True
        return on_finish

    def _make_retry_hook(self, i: int):
        def on_retry(req: Request, t_retry: float) -> None:
            # the pool already drew backoff/jitter and passed the
            # deadline check (RNG stream parity with standalone); the
            # cluster only redirects the re-enqueue through the router
            rid = req.rid
            now = self._sim.now
            self._live[rid] -= 1
            if self._where[rid] == i:
                self._where[rid] = -1
            if self._breakers is not None:
                self._breakers[i].record_error(now)
            self._pending_retry[rid] += 1
            self._pending_retry_total += 1
            self._sim.at(t_retry,
                         lambda r=req, o=i: self._route_retry(o, r))
        return on_retry

    def _make_abandon_hook(self, i: int):
        def on_abandon(req: Request) -> None:
            rid = req.rid
            self._live[rid] -= 1
            if self._where[rid] == i:
                self._where[rid] = -1
            if self._breakers is not None:
                self._breakers[i].record_error(self._sim.now)
            if (not self._completed[rid] and self._live[rid] <= 0
                    and self._pending_retry[rid] == 0):
                self._mark_lost(rid, "abandoned")
        return on_abandon

    def _make_shed_hook(self, i: int):
        def on_shed(reqs: Sequence[Request]) -> None:
            # admission control, not a failure: sheds do not feed the
            # breaker's error window
            for req in reqs:
                rid = req.rid
                self._live[rid] -= 1
                if self._where[rid] == i:
                    self._where[rid] = -1
                if (not self._completed[rid] and self._live[rid] <= 0
                        and self._pending_retry[rid] == 0):
                    self._mark_lost(rid, "shed")
        return on_shed

    def _route_retry(self, origin: int, req: Request) -> None:
        rid = req.rid
        self._pending_retry[rid] -= 1
        self._pending_retry_total -= 1
        if self._completed[rid] or self._lost[rid]:
            return
        if self._live[rid] > 0:
            # a hedge twin (or an earlier failover) is still running —
            # re-injecting would duplicate the request
            self.retries_suppressed += 1
            return
        rb = self.router.retry_budget
        if rb is not None and self._fails[rid] >= rb:
            self._mark_lost(rid, "budget")
            return
        self._fails[rid] += 1
        now = self._sim.now
        cands = self._routable(now)
        if len(cands) > 1 and origin in cands:
            # prefer failing over *away* from the pool that just lost it
            cands = [c for c in cands if c != origin]
        j = self._pick(cands, req, now)
        if j != origin:
            # a same-pool re-route is a plain retry (already counted by
            # the pool); only a cross-pool re-route is a failover
            self.n_failovers += 1
        self._live[rid] = 1
        self._where[rid] = j
        if self.record_events:
            self.routing_events.append(("failover", rid, origin, j))
        self._rts[j]._arrive(req, now)

    def _mark_lost(self, rid: int, kind: str) -> None:
        if self._lost[rid] or self._completed[rid]:
            return
        self._lost[rid] = 1
        self._resolved += 1
        self.n_lost[kind] = self.n_lost.get(kind, 0) + 1
        if self.record_events:
            self.routing_events.append(("lost", rid, kind))

    # ---- periodic machinery ---------------------------------------------

    def _tick_alive(self) -> bool:
        """Whether the health/autoscaler chains should keep running.
        Ending them lets the event heap drain — stuck requests (e.g. a
        permanently-down pool with no retries) end the run exactly as
        they do standalone, instead of ticking forever."""
        if self._resolved >= self._expected:
            return False
        if (self._pending_routes or self._pending_retry_total
                or self._pending_hedges):
            return True
        scaler = self.autoscaler is not None
        for p, rt in enumerate(self._rts):
            for rep in rt.replicas:
                if rep.busy:
                    return True
            if self._pending_orders[p]:
                return True
            if (scaler and rt.pending
                    and self._en_count[p] < len(rt.replicas)):
                return True
        return False

    def _health_tick(self) -> bool:
        now = self._sim.now
        hp = self.health
        for i, rt in enumerate(self._rts):
            en = rt._enabled
            in_rot = self._in_rot[i]
            bad, good = self._h_bad[i], self._h_good[i]
            out_since = self._out_since[i]
            down, speed = rt._down, rt._speed
            count = 0
            for r in range(len(in_rot)):
                ok = (not down[r]) and speed[r] <= hp.max_slow_factor
                if ok:
                    good[r] += 1
                    bad[r] = 0
                    if not in_rot[r] and good[r] >= hp.healthy_after:
                        in_rot[r] = True
                        self._t_out[i] += now - out_since[r]
                else:
                    bad[r] += 1
                    good[r] = 0
                    if in_rot[r] and bad[r] >= hp.unhealthy_after:
                        in_rot[r] = False
                        out_since[r] = now
                if in_rot[r] and (en is None or en[r]):
                    count += 1
            self._rotation[i] = count
        if self.probe is not None:
            self._obs_emit(now)
        return self._tick_alive()

    def _scale_tick(self) -> bool:
        now = self._sim.now
        pol = self.autoscaler
        for i, rt in enumerate(self._rts):
            en_ct = self._en_count[i]
            depth = len(rt.pending) / max(1, en_ct)
            if depth > pol.up_threshold:
                room = len(rt.replicas) - en_ct - self._pending_orders[i]
                k = min(pol.step, room)
                for _ in range(max(0, k)):
                    self._pending_orders[i] += 1
                    self._sim.at(now + pol.scale_up_lag,
                                 lambda p=i: self._activate(p))
            elif (depth < pol.down_threshold
                    and self._pending_orders[i] == 0
                    and en_ct > pol.min_replicas):
                for _ in range(min(pol.step, en_ct - pol.min_replicas)):
                    self._drain(i)
        if self.probe is not None:
            self._obs_emit(now)
        return self._tick_alive()

    def _activate(self, i: int) -> None:
        """A scale-up order arrives (after the boot/warm-up lag)."""
        self._pending_orders[i] -= 1
        rt = self._rts[i]
        en = rt._enabled
        if en is None:
            return
        for r in range(len(en)):
            if not en[r]:
                self._set_enabled(i, r, True)
                return

    def _drain(self, i: int) -> None:
        rt = self._rts[i]
        en = rt._enabled
        if en is None:
            en = rt._enabled = [True] * len(rt.replicas)
        for r in range(len(en) - 1, -1, -1):
            if en[r]:
                self._set_enabled(i, r, False)
                return

    def _set_enabled(self, i: int, r: int, flag: bool) -> None:
        now = self._sim.now
        self._en_seconds[i] += self._en_count[i] * (now - self._en_last[i])
        self._en_last[i] = now
        self._en_count[i] += 1 if flag else -1
        self.scale_events.append((now, self.pools[i].name,
                                  1 if flag else -1))
        self._rts[i].set_replica_enabled(r, flag, now)
        if self.record_events:
            self.routing_events.append(
                ("scale", self.pools[i].name, r, flag))

    # ---- observability ---------------------------------------------------

    def _obs_emit(self, now: float) -> None:
        for i in range(self._n_pools):
            self._p_rot[i].set(now, float(self._rot_count(i)))
            self._p_en[i].set(now, float(self._en_count[i]))
        for h, v in ((self._p_failover, self.n_failovers),
                     (self._p_hedges, self.hedges_issued),
                     (self._p_lost, sum(self.n_lost.values()))):
            h.value = v = float(v)
            h.series._append(now, v)

    # ---- entry point -----------------------------------------------------

    def run(self) -> ClusterReport:
        # fault schedules first (pool order): at tied timestamps fault
        # events beat arrivals, matching the standalone contract
        for rt in self._rts:
            rt._arm_faults()
        if self.health is not None:
            self._sim.every(self.health.interval, self._health_tick)
        if self.autoscaler is not None:
            self._sim.every(self.autoscaler.interval, self._scale_tick)
        for req in self.workload.initial():
            self._route_new(req)
        sim_result = self._sim.run()
        return self._build_report(sim_result)

    def _build_report(self, sim_result: SimResult) -> ClusterReport:
        end_t = max(sim_result.makespan, self._sim.now)
        pools = self.pools
        pool_reports: Dict[str, ServingReport] = {}
        for spec, rt in zip(pools, self._rts):
            pool_reports[spec.name] = rt._build_report(sim_result,
                                                       flush=False)

        # cluster latency populations: every pool's metric columns, as
        # one population (identical arithmetic to LaneStateArrays.stats)
        def cat(name: str) -> np.ndarray:
            return np.concatenate(
                [getattr(rt.lane_state, name)[:rt.lane_state.n]
                 for rt in self._rts])

        t_arrive, t_first = cat("t_arrive"), cat("t_first")
        t_done, out = cat("t_done"), cat("output")
        mask = out > 1
        tpot = ((t_done[mask] - t_first[mask]) / (out[mask] - 1)
                if mask.any() else np.empty(0))
        ttft = LatencyStats.of(t_first - t_arrive)
        tpot_s = LatencyStats.of(tpot)
        e2e = LatencyStats.of(t_done - t_arrive)
        qd = LatencyStats.of(cat("t_admit") - t_arrive)

        total_reps = sum(len(rt.replicas) for rt in self._rts)
        util = 0.0
        if sim_result.makespan > 0 and total_reps:
            busy = sum(sim_result.resource_busy.get(rt._res(r.index), 0.0)
                       for rt in self._rts for r in rt.replicas)
            util = busy / (total_reps * sim_result.makespan)

        fleet_av = 1.0
        if total_reps:
            fleet_av = sum(pool_reports[s.name].availability
                           * len(rt.replicas)
                           for s, rt in zip(pools, self._rts)) / total_reps

        trips: Dict[str, int] = {}
        open_time: Dict[str, float] = {}
        if self._breakers is not None:
            for spec, b in zip(pools, self._breakers):
                b.finalize(end_t)
                trips[spec.name] = b.n_trips
                open_time[spec.name] = b.time_open

        t_out: Dict[str, float] = {}
        if self.health is not None:
            for i, spec in enumerate(pools):
                extra = sum(end_t - self._out_since[i][r]
                            for r in range(len(self._in_rot[i]))
                            if not self._in_rot[i][r])
                t_out[spec.name] = self._t_out[i] + extra

        en_seconds: Dict[str, float] = {}
        cost = 0.0
        for i, spec in enumerate(pools):
            secs = (self._en_seconds[i]
                    + self._en_count[i] * (end_t - self._en_last[i]))
            en_seconds[spec.name] = secs
            cost += secs * spec.cost_rate

        if self.probe is not None:
            self._obs_emit(end_t)
            self.probe.flush()

        reports = list(pool_reports.values())
        return ClusterReport(
            workload=self.workload.name,
            router=self.router.name,
            pools=pool_reports,
            replicas=total_reps,
            duration=sim_result.makespan,
            n_offered=self.n_offered,
            n_requests=self.n_completed,
            output_tokens=sum(rt._total_out_tokens for rt in self._rts),
            ttft=ttft, tpot=tpot_s, e2e=e2e, queue_delay=qd,
            replica_util=util,
            availability=(self.n_completed / self.n_offered
                          if self.n_offered else 1.0),
            fleet_availability=fleet_av,
            n_failures=sum(r.n_failures for r in reports),
            n_retries=sum(r.n_retries for r in reports),
            n_failovers=self.n_failovers,
            retries_suppressed=self.retries_suppressed,
            n_failopen=self.n_failopen,
            n_abandoned=sum(r.n_abandoned for r in reports),
            n_shed=sum(r.n_shed for r in reports),
            n_lost=dict(self.n_lost),
            hedges_issued=self.hedges_issued,
            hedges_won=self.hedges_won,
            hedge_waste_tokens=self.hedge_waste_tokens,
            breaker_trips=trips,
            breaker_open_time=open_time,
            time_out_of_rotation=t_out,
            n_routed={s.name: n for s, n in zip(pools, self.n_routed)},
            scale_events=list(self.scale_events),
            enabled_seconds=en_seconds,
            cost=cost,
            events=self.routing_events)


def simulate_cluster(pools: Sequence[ReplicaPool], workload: Workload,
                     router: Optional[RouterPolicy] = None,
                     **kwargs) -> ClusterReport:
    """One-shot convenience wrapper around :class:`ClusterSimulator`."""
    return ClusterSimulator(pools, workload, router, **kwargs).run()


# ---------------------------------------------------------------------------
# Monte-Carlo cluster simulation
# ---------------------------------------------------------------------------


@dataclass
class MonteCarloClusterReport:
    """Cross-seed cluster estimate: per-seed reports + summary stats."""

    workload: str
    router: str
    pool_names: Tuple[str, ...]
    seeds: Tuple[int, ...]
    reports: List[ClusterReport]
    stats: Dict[str, "object"]

    @property
    def num_seeds(self) -> int:
        return len(self.seeds)

    def stat(self, name: str):
        return self.stats[name]

    @property
    def availability(self):
        return self.stats["availability"]

    @property
    def throughput_rps(self):
        return self.stats["throughput_rps"]

    @property
    def cost(self):
        return self.stats["cost"]

    def summary(self) -> str:
        a = self.stats["availability"]
        x = self.stats["throughput_rps"]
        e = self.stats["e2e_p99"]
        c = self.stats["cost"]
        return (
            f"mc-cluster[{self.router}|{self.workload}] "
            f"{len(self.pool_names)} pools, {self.num_seeds} seeds: "
            f"{x.mean:.2f} ± {x.half_width:.2f} req/s, "
            f"availability = {a.mean:.4%} ± {a.half_width:.4%} "
            f"(CI lo {a.ci_lo:.4%}), E2E p99 = {e.mean:.2f} ± "
            f"{e.half_width:.2f} s, cost = {c.mean:.0f}")


class MonteCarloClusterSimulator:
    """Runs a :class:`ClusterSimulator` per seed row of a
    :class:`~repro.serve_sim.workload.RequestBatch` and reduces the
    reports to cross-seed :class:`~repro.serve_sim.monte_carlo.SeedStats`.

    Each seed gets an independent fault draw per pool — pool ``i``
    compiles its :class:`~repro.serve_sim.faults.FailureModel` under
    seed ``(model.seed, i, scenario_seed)`` so pools never share outage
    schedules by accident; explicit :class:`ReplicaFault` lists stay
    deterministic across seeds (matching the standalone Monte-Carlo
    convention).  ``router_factory`` builds a *fresh* router per seed
    (routers carry mutable pick state).
    """

    def __init__(self, pools: Sequence[ReplicaPool], batch: RequestBatch,
                 router_factory: Optional[Callable[[], RouterPolicy]] = None,
                 **cluster_kwargs):
        if not isinstance(batch, RequestBatch):
            raise TypeError(f"need a RequestBatch, got {type(batch)!r}")
        if "fault_seed" in cluster_kwargs:
            raise ValueError("fault_seed is derived per seed; "
                             "set FailureModel.seed instead")
        self.pools = list(pools)
        self.batch = batch
        self.router_factory = (router_factory if router_factory is not None
                               else RoundRobinRouter)
        self.cluster_kwargs = cluster_kwargs

    def _fault_seeds(self, seed: int) -> list:
        return [((spec.failures.seed, i, seed)
                 if isinstance(spec.failures, FailureModel) else None)
                for i, spec in enumerate(self.pools)]

    def run(self) -> MonteCarloClusterReport:
        from repro.serve_sim.monte_carlo import SeedStats, _cross_seed_stats

        reports: List[ClusterReport] = []
        for k in range(self.batch.num_seeds):
            seed = int(self.batch.seeds[k])
            sim = ClusterSimulator(
                self.pools, self.batch.workload(k),
                router=self.router_factory(),
                fault_seed=self._fault_seeds(seed),
                **self.cluster_kwargs)
            reports.append(sim.run())

        stats = _cross_seed_stats(reports)
        for key, fn in (
                ("cost", lambda r: r.cost),
                ("n_failovers", lambda r: float(r.n_failovers)),
                ("hedges_issued", lambda r: float(r.hedges_issued)),
                ("hedges_won", lambda r: float(r.hedges_won)),
                ("fleet_availability", lambda r: r.fleet_availability),
                ("n_lost", lambda r: float(r.n_lost_total))):
            stats[key] = SeedStats.of([fn(r) for r in reports])
        r0 = reports[0]
        return MonteCarloClusterReport(
            workload=self.batch.name, router=r0.router,
            pool_names=tuple(p.name for p in self.pools),
            seeds=tuple(int(s) for s in self.batch.seeds),
            reports=reports, stats=stats)
