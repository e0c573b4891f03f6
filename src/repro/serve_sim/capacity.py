"""SLO-aware capacity planning over the virtual serving simulator.

The paper's top-down flow asks "what hardware annotation meets the
target?"; the serving analog asks **"what is the smallest deployment that
meets the latency SLO under this traffic?"**.  :class:`CapacityPlanner`
answers it by bisecting over replica count (or batch slots per replica)
and re-running the seeded serving simulation at each probe — every probe
is a full tail-latency estimate, not a closed-form approximation, so
burstiness and scheduler behaviour are captured.

Monotonicity note: tail latency is *not* perfectly monotone in capacity
(batching dynamics can shift percentiles slightly), so the planner runs a
doubling phase to find a feasible upper bound, then bisects — the result
is the smallest probed configuration that met the SLO with all smaller
probed configurations failing, which is the operational question.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from repro.serve_sim.cost import ServingCostModel
from repro.serve_sim.scheduler import BatchScheduler
from repro.serve_sim.simulator import ServingReport, simulate_serving
from repro.serve_sim.workload import Workload


@dataclass(frozen=True)
class SLO:
    """Latency targets (seconds); ``inf`` disables a term.

    ``availability`` is a degraded-mode floor (fraction of replica-seconds
    up over the run; 0.0 disables it): under a fault profile a deployment
    only counts as feasible when it also keeps the fleet available — this
    is what makes the planner's answer an N+1-style redundancy sizing
    rather than a pure latency sizing."""

    ttft_p99: float = math.inf
    tpot_p99: float = math.inf
    e2e_p99: float = math.inf
    availability: float = 0.0

    def satisfied_by(self, report: ServingReport) -> bool:
        return (report.ttft.p99 <= self.ttft_p99
                and report.tpot.p99 <= self.tpot_p99
                and report.e2e.p99 <= self.e2e_p99
                and report.availability >= self.availability)

    def satisfied_by_ci(self, report) -> bool:
        """CI-conservative attainment for a seed-batched
        :class:`~repro.serve_sim.monte_carlo.MonteCarloServingReport`:
        every constrained metric must meet its target at the *upper* 95%
        confidence bound of the cross-seed mean (availability at the
        *lower* bound), so one lucky draw cannot declare a configuration
        feasible."""
        return (report.stat("ttft_p99").ci_hi <= self.ttft_p99
                and report.stat("tpot_p99").ci_hi <= self.tpot_p99
                and report.stat("e2e_p99").ci_hi <= self.e2e_p99
                and (self.availability <= 0.0
                     or report.stat("availability").ci_lo
                     >= self.availability))

    def __str__(self) -> str:
        terms = []
        if math.isfinite(self.ttft_p99):
            terms.append(f"TTFT p99<={self.ttft_p99 * 1e3:.0f}ms")
        if math.isfinite(self.tpot_p99):
            terms.append(f"TPOT p99<={self.tpot_p99 * 1e3:.1f}ms")
        if math.isfinite(self.e2e_p99):
            terms.append(f"E2E p99<={self.e2e_p99:.1f}s")
        if self.availability > 0.0:
            terms.append(f"avail>={self.availability:.3%}")
        return " & ".join(terms) or "no SLO"


@dataclass
class CapacityPlan:
    """Outcome of one planning run."""

    axis: str                      # "replicas" | "slots"
    value: int                     # smallest feasible probe (or cap if none)
    feasible: bool
    #: the winning probe's :class:`ServingReport` — or, when the planner
    #: ran with ``num_seeds > 1``, its ``MonteCarloServingReport``
    report: Optional[object]
    probes: Dict[int, bool] = field(default_factory=dict)

    def __str__(self) -> str:
        status = "meets SLO" if self.feasible else "INFEASIBLE at cap"
        return f"{self.axis}={self.value} ({status}, {len(self.probes)} probes)"


class CapacityPlanner:
    """Finds the smallest deployment meeting an :class:`SLO`.

    ``workload_factory`` must return a *fresh, identically-seeded* workload
    per call (closed-loop workloads are stateful); likewise
    ``scheduler_factory`` returns a fresh policy per replica.

    With ``num_seeds > 1`` the factory must instead return a
    ``repro.serve_sim.workload.RequestBatch`` with that many rows; every
    probe then runs the seed-batched Monte-Carlo simulator and the
    bisection decides feasibility on the cross-seed confidence interval
    (:meth:`SLO.satisfied_by_ci`) instead of a single draw — a
    configuration only counts as feasible when the upper 95% bound of
    each constrained p99 meets its target.
    """

    def __init__(self, cost: ServingCostModel,
                 scheduler_factory: Callable[[], BatchScheduler],
                 workload_factory: Callable[[], Workload],
                 slo: SLO, num_seeds: int = 1,
                 failures=None, retry=None):
        """``failures``/``retry`` (see
        :class:`~repro.serve_sim.faults.FailureModel` /
        :class:`~repro.serve_sim.faults.RetryPolicy`) inject the same
        fault profile into every probe, so the plan answers "what is the
        smallest deployment that meets the SLO *while replicas churn*" —
        with an ``SLO.availability`` floor and ``num_seeds > 1`` this is
        an N+1 redundancy bisection against the cross-seed CI."""
        if num_seeds < 1:
            raise ValueError("need num_seeds >= 1")
        self.cost = cost
        self.scheduler_factory = scheduler_factory
        self.workload_factory = workload_factory
        self.slo = slo
        self.num_seeds = num_seeds
        self.failures = failures
        self.retry = retry

    def _evaluate(self, replicas: int, slots: int):
        if self.num_seeds > 1:
            from repro.serve_sim.monte_carlo import MonteCarloServingSimulator
            from repro.serve_sim.workload import RequestBatch

            batch = self.workload_factory()
            if not isinstance(batch, RequestBatch):
                raise TypeError(
                    "num_seeds > 1 needs a workload_factory returning a "
                    f"RequestBatch, got {type(batch)!r}")
            if batch.num_seeds != self.num_seeds:
                raise ValueError(f"batch has {batch.num_seeds} seed rows, "
                                 f"planner wants {self.num_seeds}")
            return MonteCarloServingSimulator(
                self.cost, self.scheduler_factory, batch,
                replicas=replicas, slots=slots,
                failures=self.failures, retry=self.retry).run()
        return simulate_serving(self.cost, self.scheduler_factory,
                                self.workload_factory(),
                                replicas=replicas, slots=slots,
                                failures=self.failures, retry=self.retry)

    def _feasible(self, report) -> bool:
        if self.num_seeds > 1:
            return self.slo.satisfied_by_ci(report)
        return self.slo.satisfied_by(report)

    def plan(self, axis: str = "replicas", lo: int = 1, cap: int = 64,
             replicas: int = 1, slots: int = 8) -> CapacityPlan:
        """Bisect ``axis`` in ``[lo, cap]`` for the smallest SLO-feasible
        value; the other dimension is fixed (``replicas`` / ``slots``)."""
        if axis not in ("replicas", "slots"):
            raise ValueError("axis must be 'replicas' or 'slots'")

        def evaluate(v: int):
            return self._evaluate(v if axis == "replicas" else replicas,
                                  v if axis == "slots" else slots)

        value, ok, probes, reports = _plan_bisect(
            evaluate, self._feasible, lo, cap)
        return CapacityPlan(axis=axis, value=value, feasible=ok,
                            report=reports.get(value), probes=probes)


def _plan_bisect(evaluate: Callable[[int], object],
                 is_feasible: Callable[[object], bool],
                 lo: int, cap: int):
    """Shared doubling-then-bisect search for the smallest feasible value
    in ``[lo, cap]`` (see the monotonicity note in the module docstring).
    Returns ``(value, feasible, probes, reports)``; when nothing in range
    is feasible, ``value`` is ``cap`` with ``feasible=False``."""
    if lo < 1 or cap < lo:
        raise ValueError(f"need 1 <= lo <= cap, got lo={lo}, cap={cap}")

    probes: Dict[int, bool] = {}
    reports: Dict[int, object] = {}

    def feasible(v: int) -> bool:
        if v not in probes:
            r = evaluate(v)
            reports[v] = r
            probes[v] = is_feasible(r)
        return probes[v]

    # doubling phase: find a feasible upper bound
    hi = lo
    while hi < cap and not feasible(hi):
        hi = min(cap, hi * 2)
    if not feasible(hi):
        return hi, False, probes, reports
    # bisect down to the smallest feasible probe
    lo_infeasible = max((v for v, ok in probes.items() if not ok),
                        default=lo - 1)
    best = hi
    lo_b, hi_b = lo_infeasible + 1, hi
    while lo_b < hi_b:
        mid = (lo_b + hi_b) // 2
        if feasible(mid):
            best = mid
            hi_b = mid
        else:
            lo_b = mid + 1
    return best, True, probes, reports


@dataclass
class RedundancyPlan:
    """Outcome of an N+k redundancy comparison
    (:meth:`ClusterCapacityPlanner.plan_redundancy`)."""

    base: int                       # the N of N+k (replicas per pool)
    options: Dict[int, bool]        # extra k -> SLO-feasible?
    choice: Optional[int]           # smallest feasible k (None: none were)
    reports: Dict[int, object] = field(default_factory=dict)

    @property
    def feasible(self) -> bool:
        return self.choice is not None

    def __str__(self) -> str:
        opts = ", ".join(f"N+{k}:{'ok' if ok else 'MISS'}"
                         for k, ok in sorted(self.options.items()))
        if self.choice is None:
            return f"no N+k option meets the SLO (N={self.base}; {opts})"
        return f"N+{self.choice} meets the SLO (N={self.base}; {opts})"


class ClusterCapacityPlanner:
    """Cluster mode of the capacity planner: sizes *per-pool* replica
    counts for a heterogeneous routed cluster under a fault profile.

    ``pools_factory(n)`` must return the cluster's pool list scaled to
    ``n`` replicas per pool (each pool carrying its own cost model and
    :class:`~repro.serve_sim.faults.FailureModel`); ``workload_factory``
    returns a fresh workload per probe — or, with ``num_seeds > 1``, a
    ``RequestBatch`` with that many seed rows, in which case every probe
    runs the Monte-Carlo cluster simulator and feasibility is decided on
    cross-seed confidence bounds (:meth:`SLO.satisfied_by_ci` — the
    availability floor reads the *lower* CI bound, so one lucky fault
    draw cannot declare a redundancy level sufficient).

    Remaining keyword arguments (``health=``, ``hedge=``, ``breaker=``,
    ``autoscaler=``, ``phase_tasks=`` ...) are forwarded to every
    :class:`~repro.serve_sim.cluster.ClusterSimulator` probe.
    """

    def __init__(self, pools_factory: Callable[[int], list],
                 workload_factory: Callable[[], object],
                 slo: SLO,
                 router_factory: Optional[Callable[[], object]] = None,
                 num_seeds: int = 1,
                 **cluster_kwargs):
        if num_seeds < 1:
            raise ValueError("need num_seeds >= 1")
        self.pools_factory = pools_factory
        self.workload_factory = workload_factory
        self.slo = slo
        self.router_factory = router_factory
        self.num_seeds = num_seeds
        self.cluster_kwargs = cluster_kwargs

    def _evaluate(self, n: int):
        from repro.serve_sim.cluster import (ClusterSimulator,
                                             MonteCarloClusterSimulator)
        from repro.serve_sim.workload import RequestBatch

        pools = self.pools_factory(n)
        if self.num_seeds > 1:
            batch = self.workload_factory()
            if not isinstance(batch, RequestBatch):
                raise TypeError(
                    "num_seeds > 1 needs a workload_factory returning a "
                    f"RequestBatch, got {type(batch)!r}")
            if batch.num_seeds != self.num_seeds:
                raise ValueError(f"batch has {batch.num_seeds} seed rows, "
                                 f"planner wants {self.num_seeds}")
            return MonteCarloClusterSimulator(
                pools, batch, router_factory=self.router_factory,
                **self.cluster_kwargs).run()
        router = (self.router_factory()
                  if self.router_factory is not None else None)
        return ClusterSimulator(pools, self.workload_factory(), router,
                                **self.cluster_kwargs).run()

    def _feasible(self, report) -> bool:
        if self.num_seeds > 1:
            return self.slo.satisfied_by_ci(report)
        return self.slo.satisfied_by(report)

    def plan(self, lo: int = 1, cap: int = 64) -> CapacityPlan:
        """Smallest per-pool replica count in ``[lo, cap]`` meeting the
        SLO (doubling then bisection, like the single-pool planner)."""
        value, ok, probes, reports = _plan_bisect(
            self._evaluate, self._feasible, lo, cap)
        return CapacityPlan(axis="replicas_per_pool", value=value,
                            feasible=ok, report=reports.get(value),
                            probes=probes)

    def plan_redundancy(self, base: int,
                        extras=(0, 1, 2)) -> RedundancyPlan:
        """The N+1-vs-N+2 question: probe ``base + k`` replicas per pool
        for each ``k`` in ``extras`` and pick the smallest feasible
        overprovision — with ``num_seeds > 1`` each verdict is backed by
        the cross-seed CI availability bound."""
        if base < 1:
            raise ValueError("base must be >= 1")
        options: Dict[int, bool] = {}
        reports: Dict[int, object] = {}
        choice: Optional[int] = None
        for k in sorted(set(int(e) for e in extras)):
            if k < 0:
                raise ValueError("extras must be >= 0")
            r = self._evaluate(base + k)
            reports[k] = r
            ok = self._feasible(r)
            options[k] = ok
            if ok and choice is None:
                choice = k
        return RedundancyPlan(base=base, options=options, choice=choice,
                              reports=reports)
