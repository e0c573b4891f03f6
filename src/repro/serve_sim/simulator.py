"""Traffic-driven serving simulator over the extended DES engine.

Where ``repro.core.estimator`` answers *"how long is one static step?"*,
this module answers the ROADMAP's serving question at the concept phase:
*"what tail latency does this system + scheduler sustain under this
traffic?"* — before any prototype exists.

Mechanics: every request arrival is a timed callback
(:meth:`~repro.core.sim.engine.DynamicSimulator.at`) on the DES engine;
each scheduler decision (prefill batch, decode step) is submitted as one
phase on the replica's service lane, with durations from the :class:`~repro.serve_sim.cost.ServingCostModel` (itself
derived from a compiled task graph, so what-if re-annotation flows through
to serving metrics).  Completion callbacks drive the scheduler causally:
finish a request, free its slot, admit the next, issue the next step.

The emitted :class:`ServingReport` carries throughput, replica
utilization, and the serving tail metrics — TTFT (arrival to first
generated token), TPOT (mean inter-token time after the first), and E2E
latency — at p50/p95/p99, plus the raw per-request rows and the engine's
``SimResult`` for Gantt / Chrome-trace export
(:func:`repro.core.sim.trace.serving_chrome_trace`).

The measured counterpart is ``repro.launch.serve.BatchedServer``, which
logs the same per-request TTFT/TPOT — the paper's predicted-vs-measured
accuracy loop, extended to serving.
"""
from __future__ import annotations

from bisect import bisect_left, insort
from collections import deque
from dataclasses import dataclass, field
from heapq import heappop, heappush
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.sim.engine import (DynamicSimulator, GraphTemplate,
                                   SimResult, Task)
from repro.serve_sim.cost import ServingCostModel
from repro.serve_sim.faults import RetryPolicy, compile_faults
from repro.serve_sim.scheduler import (BatchScheduler, Decode, InFlight,
                                       Prefill, ReplicaState, Shed, Wait)
from repro.serve_sim.workload import Request, Workload


@dataclass(frozen=True)
class LatencyStats:
    """Percentile summary of one latency population (seconds)."""

    n: int
    mean: float
    p50: float
    p95: float
    p99: float
    max: float

    @staticmethod
    def of(values) -> "LatencyStats":
        """Summarize a list or 1-D array of latency values."""
        if len(values) == 0:
            return LatencyStats(0, 0.0, 0.0, 0.0, 0.0, 0.0)
        a = np.asarray(values, dtype=np.float64)
        p50, p95, p99 = np.percentile(a, (50, 95, 99))
        return LatencyStats(n=len(a), mean=float(a.mean()), p50=float(p50),
                            p95=float(p95), p99=float(p99),
                            max=float(a.max()))


@dataclass
class RequestMetrics:
    """Per-request outcome (the rows behind the percentiles)."""

    rid: int
    replica: int
    slot: int
    t_arrive: float
    t_admit: float
    t_first: float
    t_done: float
    prompt_tokens: int
    output_tokens: int

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_arrive

    @property
    def queue_delay(self) -> float:
        return self.t_admit - self.t_arrive

    @property
    def e2e(self) -> float:
        return self.t_done - self.t_arrive

    @property
    def tpot(self) -> float:
        n = self.output_tokens
        return (self.t_done - self.t_first) / (n - 1) if n > 1 else 0.0


class LaneStateArrays:
    """Structure-of-arrays serving state — the *state advance* half of the
    simulator split.

    The serving hot loop separates into branchy per-lane *policy
    decisions* (which request to admit, when to decode — driven by
    :class:`~repro.serve_sim.scheduler.BatchScheduler` and the per-lane
    event machinery) and a uniform *state advance* (arrival/admit/first/
    finish timestamps, slot placement, token counts) that is identical
    arithmetic for every request.  This class holds the advance side as
    flat NumPy columns: the scalar :class:`ServingSimulator` records each
    finished request into one instance, and the seed-batched
    :class:`~repro.serve_sim.monte_carlo.MonteCarloServingSimulator`
    allocates one per seed so cross-seed statistics reduce to vectorized
    column arithmetic.

    Latency populations (TTFT/TPOT/E2E/queue delay) are derived from the
    columns bit-identically to the per-row :class:`RequestMetrics`
    properties they replace; ``RequestMetrics`` rows themselves are
    materialized lazily (:class:`_LazyRequests`) only when a consumer
    asks for them.
    """

    __slots__ = ("n", "rid", "replica", "slot", "t_arrive", "t_admit",
                 "t_first", "t_done", "prompt", "output")

    def __init__(self, capacity: int = 0):
        cap = max(int(capacity), 16)
        self.n = 0
        self.rid = np.empty(cap, np.int64)
        self.replica = np.empty(cap, np.int32)
        self.slot = np.empty(cap, np.int32)
        self.t_arrive = np.empty(cap, np.float64)
        self.t_admit = np.empty(cap, np.float64)
        self.t_first = np.empty(cap, np.float64)
        self.t_done = np.empty(cap, np.float64)
        self.prompt = np.empty(cap, np.int64)
        self.output = np.empty(cap, np.int64)

    def _grow(self) -> None:
        for name in self.__slots__[1:]:
            col = getattr(self, name)
            new = np.empty(2 * len(col), col.dtype)
            new[:self.n] = col[:self.n]
            setattr(self, name, new)

    def record(self, rid: int, replica: int, slot: int, t_arrive: float,
               t_admit: float, t_first: float, t_done: float,
               prompt: int, output: int) -> None:
        i = self.n
        if i >= len(self.rid):
            self._grow()
        self.rid[i] = rid
        self.replica[i] = replica
        self.slot[i] = slot
        self.t_arrive[i] = t_arrive
        self.t_admit[i] = t_admit
        self.t_first[i] = t_first
        self.t_done[i] = t_done
        self.prompt[i] = prompt
        self.output[i] = output
        self.n = i + 1

    def sort_by_rid(self) -> None:
        n = self.n
        order = np.argsort(self.rid[:n], kind="stable")
        for name in self.__slots__[1:]:
            col = getattr(self, name)
            col[:n] = col[:n][order]

    # ---- derived latency populations (vectorized column arithmetic) ----

    def stats(self) -> Tuple["LatencyStats", "LatencyStats",
                             "LatencyStats", "LatencyStats"]:
        """(ttft, tpot, e2e, queue_delay) percentile summaries."""
        n = self.n
        t_arrive = self.t_arrive[:n]
        t_first = self.t_first[:n]
        t_done = self.t_done[:n]
        out = self.output[:n]
        mask = out > 1
        tpot = (t_done[mask] - t_first[mask]) / (out[mask] - 1)
        return (LatencyStats.of(t_first - t_arrive),
                LatencyStats.of(tpot),
                LatencyStats.of(t_done - t_arrive),
                LatencyStats.of(self.t_admit[:n] - t_arrive))

    def to_request_metrics(self) -> List["RequestMetrics"]:
        return [RequestMetrics(
            rid=int(self.rid[i]), replica=int(self.replica[i]),
            slot=int(self.slot[i]), t_arrive=float(self.t_arrive[i]),
            t_admit=float(self.t_admit[i]), t_first=float(self.t_first[i]),
            t_done=float(self.t_done[i]), prompt_tokens=int(self.prompt[i]),
            output_tokens=int(self.output[i])) for i in range(self.n)]


class _LazyRequests(Sequence):
    """Sequence view over :class:`LaneStateArrays` that materializes
    :class:`RequestMetrics` rows on first access — reports stay cheap to
    build and to pickle (only the columns cross process boundaries)."""

    __slots__ = ("_arrays", "_rows")

    def __init__(self, arrays: LaneStateArrays):
        self._arrays = arrays
        self._rows: Optional[List[RequestMetrics]] = None

    def _materialize(self) -> List[RequestMetrics]:
        if self._rows is None:
            self._rows = self._arrays.to_request_metrics()
        return self._rows

    def __len__(self) -> int:
        return self._arrays.n

    def __bool__(self) -> bool:
        return self._arrays.n > 0

    def __getitem__(self, i):
        return self._materialize()[i]

    def __iter__(self):
        return iter(self._materialize())

    def __reduce__(self):
        return (_LazyRequests, (self._arrays,))


#: leap length from which the fused-step accumulation switches to
#: ``np.add.accumulate`` (same left-to-right addition order as the Python
#: loop, so the switch is bit-invisible; below this the loop is faster).
_LEAP_NUMPY_MIN = 16

#: shared step-index cache for the numpy leap path (grown on demand;
#: read-only views are sliced out, so sharing across simulators is safe)
_ARANGE = np.arange(1024, dtype=np.int64)


def _arange1(k: int) -> np.ndarray:
    """Cached ``np.arange(1, k)`` view."""
    global _ARANGE
    if k > len(_ARANGE):
        _ARANGE = np.arange(max(k, 2 * len(_ARANGE)), dtype=np.int64)
    return _ARANGE[1:k]


class _LeapScratch:
    """Reusable buffers for :func:`_leap_spans`' numpy path — one fused
    decode leap per call makes the per-call ``np.empty``/``np.arange``
    allocations the hot path's dominant constant; a scratch instance per
    simulator removes them without touching the arithmetic."""

    __slots__ = ("f", "i")

    def __init__(self):
        self.f = np.empty(64)
        self.i = np.empty(64, np.int64)

    def resize(self, k: int) -> None:
        if len(self.f) < k:
            n = max(k, 2 * len(self.f))
            self.f = np.empty(n)
            self.i = np.empty(n, np.int64)


def _leap_spans(now: float, c0: float, base: float, c_d: float,
                ctx: int, n_dec: int, k: int, speculate: bool,
                scratch: Optional[_LeapScratch] = None):
    """Fused decode-leap state advance under the affine cost model.

    Accumulates the exact per-step costs of a ``k``-step leap starting
    from ``ctx`` cached tokens (``base = decode_fixed +
    decode_per_token * n`` is the ctx-independent part of one step).
    Returns ``(total_duration, bounds)`` where ``bounds`` are the
    absolute per-step boundary times (only when ``speculate`` — they arm
    the rollback) — bit-identical whether the sequential Python loop or
    the vectorized ``np.add.accumulate`` path ran (and whether or not a
    ``scratch`` buffer set is supplied: every elementwise op and the
    left-to-right accumulation order are unchanged).
    """
    if k >= _LEAP_NUMPY_MIN:
        ar = _arange1(k)
        if scratch is not None:
            scratch.resize(k)
            steps = scratch.f[:k]
            ints = scratch.i[1:k]
            np.multiply(ar, n_dec, out=ints)
            np.add(ints, ctx, out=ints)
            tail = steps[1:]
            np.multiply(ints, c_d, out=tail)
            np.add(tail, base, out=tail)
            steps[0] = c0
            cum = np.add.accumulate(steps, out=steps)
        else:
            steps = np.empty(k)
            steps[0] = c0
            steps[1:] = base + c_d * (ctx + n_dec * ar)
            cum = np.add.accumulate(steps)
        return float(cum[-1]), (now + cum if speculate else None)
    dur = c0
    if speculate:
        bounds = [now + c0]
        for _ in range(k - 1):
            ctx += n_dec
            dur += base + c_d * ctx
            bounds.append(now + dur)
        return dur, bounds
    for _ in range(k - 1):
        ctx += n_dec
        dur += base + c_d * ctx
    return dur, None


@dataclass
class ServingReport:
    """End-to-end serving estimate for one (system, scheduler, traffic)."""

    workload: str
    scheduler: str
    cost_model: str
    replicas: int
    slots: int
    n_requests: int
    duration: float                    # makespan, seconds
    output_tokens: int
    ttft: LatencyStats
    tpot: LatencyStats
    e2e: LatencyStats
    queue_delay: LatencyStats
    replica_util: float                # mean busy fraction across replicas
    #: per-request rows; a list, or a :class:`_LazyRequests` view that
    #: materializes :class:`RequestMetrics` on first access
    requests: Sequence[RequestMetrics] = field(default_factory=list)
    sim_result: Optional[SimResult] = None
    events: List[Tuple] = field(default_factory=list)
    # ---- resilience metrics (fault-injection runs; defaults = no faults) --
    n_offered: int = 0          # requests that ever arrived (excl. retries)
    n_failures: int = 0         # replica failure windows begun by makespan
    n_retries: int = 0          # re-enqueues after a replica crash
    n_abandoned: int = 0        # dropped: retry budget / deadline exhausted
    n_shed: int = 0             # dropped at admission (load shedding)
    #: shed counts keyed by request priority class — the audit-friendly
    #: breakdown behind ``n_shed`` (always sums to it)
    shed_by_priority: Dict[int, int] = field(default_factory=dict)
    availability: float = 1.0   # up replica-seconds / total replica-seconds

    @property
    def throughput_rps(self) -> float:
        return self.n_requests / self.duration if self.duration > 0 else 0.0

    @property
    def throughput_tps(self) -> float:
        return self.output_tokens / self.duration if self.duration > 0 else 0.0

    @property
    def goodput_rps(self) -> float:
        """Completed requests per second — under faults this is the rate
        of *delivered* work (retried attempts are not double-counted)."""
        return self.throughput_rps

    @property
    def attempt_rps(self) -> float:
        """Retry-amplified attempt rate: completed + retried attempts per
        second.  ``attempt_rps / goodput_rps`` is the amplification the
        fleet actually pays for the goodput it delivers."""
        if self.duration <= 0:
            return 0.0
        return (self.n_requests + self.n_retries) / self.duration

    @property
    def abandonment_rate(self) -> float:
        """Fraction of offered requests never served (abandoned after
        retries/deadline, or shed at admission)."""
        if self.n_offered <= 0:
            return 0.0
        return (self.n_abandoned + self.n_shed) / self.n_offered

    def slo_attainment(self, slo) -> float:
        """Per-request SLO attainment: the fraction of *offered* requests
        individually meeting every target of ``slo`` (its p99 fields read
        as per-request bounds here).  Abandoned and shed requests count
        as misses, so churn shows up even when the survivors' percentiles
        look healthy.  Returns 1.0 for an empty run."""
        if self.n_offered > 0:
            denom = self.n_offered
        else:
            denom = len(self.requests)
        if denom == 0:
            return 1.0
        ok = 0
        for r in self.requests:
            if (r.ttft <= slo.ttft_p99 and r.tpot <= slo.tpot_p99
                    and r.e2e <= slo.e2e_p99):
                ok += 1
        return ok / denom

    def summary(self) -> str:
        s = (
            f"serve[{self.cost_model}|{self.scheduler}|{self.workload}] "
            f"{self.replicas}x{self.slots} slots: "
            f"{self.n_requests} reqs in {self.duration:.1f}s "
            f"({self.throughput_rps:.2f} req/s, {self.throughput_tps:.1f} "
            f"tok/s, util={self.replica_util:.1%})\n"
            f"  TTFT p50/p95/p99 = {self.ttft.p50 * 1e3:.0f}/"
            f"{self.ttft.p95 * 1e3:.0f}/{self.ttft.p99 * 1e3:.0f} ms   "
            f"TPOT p50/p99 = {self.tpot.p50 * 1e3:.2f}/"
            f"{self.tpot.p99 * 1e3:.2f} ms   "
            f"E2E p99 = {self.e2e.p99:.2f} s")
        if (self.n_failures or self.n_retries or self.n_abandoned
                or self.n_shed or self.availability < 1.0):
            s += (
                f"\n  faults: {self.n_failures} failures, "
                f"{self.n_retries} retries "
                f"({self.attempt_rps:.2f} attempt/s vs "
                f"{self.goodput_rps:.2f} goodput/s), "
                f"{self.n_abandoned} abandoned + {self.n_shed} shed "
                f"({self.abandonment_rate:.1%} of offered), "
                f"availability={self.availability:.4%}")
        return s


def _slot_of(fl: InFlight) -> int:
    return fl.slot


#: queue view a drained (autoscaler-disabled) replica consults — always
#: empty, so schedulers admit nothing while in-flight work runs down.
_EMPTY_PENDING: deque = deque()


class ServingSimulator:
    """Replays a :class:`Workload` against replicas of one cost model.

    ``scheduler_factory`` is called once per replica (schedulers are
    per-replica state-free policies); ``record_events`` keeps the
    admit/step/finish sequence for scheduler-parity assertions against the
    real ``BatchedServer``.
    """

    def __init__(self, cost: ServingCostModel,
                 scheduler_factory: Callable[[], BatchScheduler],
                 workload: Workload,
                 replicas: int = 1,
                 slots: int = 8,
                 record_events: bool = False,
                 phase_tasks: int = 0,
                 probe=None,
                 probe_engine: bool = False,
                 failures=None,
                 retry: Optional[RetryPolicy] = None,
                 fault_seed=None,
                 sim=None,
                 res_prefix: str = "",
                 obs_ns: str = "serve"):
        """``phase_tasks > 0`` switches from the ServiceLane express path
        to *full task-graph mode*: every prefill/decode phase carries a
        real task graph (chained compute chunks, each followed by a
        KV-write DMA on a sibling resource), and each replica runs as a
        :class:`~repro.core.sim.engine.TemplateLane`: one event per phase,
        speculative decode leaps booked as burst entries that truncate on
        rollback.  Chunk durations either
        exact-split the phase cost or, when the cost model carries
        compiled-graph :class:`~repro.serve_sim.cost.PhaseProfile`\\ s,
        follow the compiled prefill/decode graphs' real compute/DMA
        structure — either way the chunk chain's total is the exact phase
        cost, so serving metrics match the express path to float
        round-off while traces show intra-phase overlap.  ``probe`` (a
        :class:`repro.obs.probe.Probe`) enables queue-depth/occupancy/
        leap instrumentation; probes only read state, so instrumented
        runs stay bit-identical.  ``probe_engine=True`` additionally
        threads the probe into the embedded engine (per-event
        completion counters — deeper but ~2x the instrumentation cost,
        and the replica span tracks already cover the engine's view).

        ``failures`` (a :class:`~repro.serve_sim.faults.FailureModel` or
        an explicit :class:`~repro.serve_sim.faults.ReplicaFault` list)
        injects seeded replica failures as DES events: a crash cancels
        the replica's in-flight phase via the lane epoch machinery and
        re-enqueues its requests under ``retry`` (default
        :class:`~repro.serve_sim.faults.RetryPolicy`), a slow-degrade
        window scales phases *started* inside it.  ``fault_seed``
        overrides the model's seed (the Monte-Carlo simulator threads
        per-scenario seeds through it).

        ``sim``/``res_prefix``/``obs_ns`` exist for
        :class:`repro.serve_sim.cluster.ClusterSimulator`, which runs
        several pools as one discrete-event simulation: ``sim`` shares
        an already-built engine (the caller owns scheduling order and
        ``run()``), ``res_prefix`` namespaces the per-replica resources
        (``poolA/replica0``), and ``obs_ns`` namespaces the probe
        tracks.  Left at their defaults the behavior is bit-identical
        to earlier revisions."""
        if replicas < 1 or slots < 1:
            raise ValueError("need replicas >= 1 and slots >= 1")
        if phase_tasks < 0:
            raise ValueError("phase_tasks must be >= 0")
        self.cost = cost
        self.workload = workload
        self.res_prefix = res_prefix
        self._obs_ns = obs_ns
        self.replicas = [ReplicaState(index=r, slots=slots)
                         for r in range(replicas)]
        self.schedulers = [scheduler_factory() for _ in range(replicas)]
        self.slots = slots
        self.record_events = record_events
        self.phase_tasks = int(phase_tasks)
        self.events: List[Tuple] = []
        self.pending: deque = deque()
        try:
            cap = int(workload.n_requests)
        except Exception:
            cap = 0
        self.lane_state = LaneStateArrays(capacity=cap)
        self._templates: Dict[Tuple[int, str], GraphTemplate] = {}
        # Probe handles are bound once here; every hot-path site guards on
        # a single ``is not None`` branch so disabled runs pay one branch.
        # Enabled sites only bump plain-int accumulators and a shared
        # countdown (``_obs_left``); every ``probe.sample_every``-th
        # serving event, :meth:`_obs_tick` appends one aligned sample to
        # every serving track.  That keeps the per-event cost to a few
        # integer slot ops instead of a handle method call per metric.
        self.probe = probe
        if probe is not None:
            ns = obs_ns
            self._p_queue = probe.counter(f"{ns}/queue_depth",
                                          unit="requests")
            self._p_completed = probe.counter(f"{ns}/completed",
                                              unit="requests")
            self._p_leaps = probe.counter(f"{ns}/leap_steps", unit="steps")
            self._p_spec = probe.counter(f"{ns}/spec_leaps")
            self._p_rollbacks = probe.counter(f"{ns}/rollbacks")
            self._p_failures = probe.counter(f"{ns}/failures")
            self._p_retries = probe.counter(f"{ns}/retries",
                                            unit="requests")
            self._p_abandoned = probe.counter(f"{ns}/abandoned",
                                              unit="requests")
            self._p_shed = probe.counter(f"{ns}/shed", unit="requests")
            self._p_occ = [probe.gauge(f"{ns}/replica{r}/occupancy",
                                       unit="slots")
                           for r in range(replicas)]
            self._obs_every = probe.sample_every
            self._obs_left = self._obs_every
            self._n_queue = 0
            self._n_completed = 0
            self._n_leap_steps = 0
            self._n_spec = 0
            self._n_rollbacks = 0
        else:
            self._p_queue = None
            self._p_completed = None
            self._p_leaps = None
            self._p_spec = None
            self._p_rollbacks = None
            self._p_failures = None
            self._p_retries = None
            self._p_abandoned = None
            self._p_shed = None
            self._p_occ = None
        # Graph-mode chunk structure: compiled-graph profiles when the
        # cost model carries them (chunk count comes from the profile),
        # else the synthetic equal split into ``phase_tasks`` chunks.
        pp = getattr(cost, "prefill_profile", None) if self.phase_tasks \
            else None
        dp = getattr(cost, "decode_profile", None) if self.phase_tasks \
            else None
        self._profiles = {"prefill": pp, "decode": dp}
        self._chunks = {
            "prefill": len(pp.compute) if pp is not None else self.phase_tasks,
            "decode": len(dp.compute) if dp is not None else self.phase_tasks,
        }
        self._sim = sim if sim is not None else DynamicSimulator(
            probe=probe if probe_engine else None)
        if self.phase_tasks:
            # Graph mode: each replica is a TemplateLane — full chunk/DMA
            # records per phase, one heap event per phase (and per fused
            # leap), and burst truncation for speculative rollback.
            self._lanes = [
                self._sim.template_lane(self._res(r),
                                        step_durs=self._burst_step_durs)
                for r in range(replicas)]
        else:
            # Express path: each replica is a ServiceLane (one phase at a
            # time on a dedicated single-server resource) — no Task
            # construction or dependency bookkeeping per decode step,
            # record names deferred.
            self._lanes = [self._sim.lane(self._res(r),
                                          name_fn=self._name_fn(r))
                           for r in range(replicas)]
        # Completion handlers are bound once per replica, not per step.
        self._phase_done = [self._phase_handler(rep) for rep in self.replicas]
        self._decode_done = [self._decode_handler(rep)
                             for rep in self.replicas]
        # Free slots per replica as min-heaps: admission pops the lowest
        # slot id (the order the old sorted-set-difference scan produced).
        self._free_slots = [list(range(slots)) for _ in range(replicas)]
        # Decode-leap state: steps fused into the in-flight decode task and
        # the exact end time of its first step (token-1 emission).
        self._decode_k = [1] * replicas
        self._decode_tfirst = [0.0] * replicas
        # Speculative-leap state per replica: (per-step boundary times,
        # batch width) while a rollback-able fused decode is in flight.
        self._leap: List[Optional[Tuple[List[float], int]]] = \
            [None] * replicas
        self._total_out_tokens = 0
        self._wait_until: Dict[int, float] = {}   # replica -> armed wake-up
        self._leap_scratch = _LeapScratch()
        # ---- fault injection --------------------------------------------
        self.retry = retry if retry is not None else RetryPolicy()
        self._faults = (compile_faults(failures, replicas, seed=fault_seed)
                        if failures is not None else None)
        self._fault_rng = (self._faults.rng() if self._faults is not None
                           else None)
        self._down = [False] * replicas        # crash windows (no admission)
        self._speed = [1.0] * replicas         # slow-degrade cost factor
        self._attempts: Dict[int, int] = {}    # rid -> crashes survived
        # (step boundaries, n_dec) of an in-flight fused decode: a crash
        # mid-leap commits the tokens of the steps whose boundary precedes
        # it — exactly what the per-step baseline would have delivered
        self._fault_bounds: List[Optional[Tuple]] = [None] * replicas
        self._n_offered = 0
        self._n_fail_events = 0                # obs track (incl. post-run)
        self._n_retries = 0
        self._n_abandoned = 0
        self._n_shed = 0
        self._shed_by_priority: Dict[int, int] = {}
        # ---- cluster hooks (repro.serve_sim.cluster) --------------------
        # All default to None / empty and every hot site guards on one
        # ``is not None`` (the probe pattern), so standalone runs and a
        # 1-pool pass-through cluster stay bit-identical.  The hooks do
        # bookkeeping only — no RNG draws, no event scheduling of their
        # own on the parity path.
        self._route_hook: Optional[Callable[[Request], None]] = None
        self._retry_hook: Optional[Callable[[Request, float], None]] = None
        self._abandon_hook: Optional[Callable[[Request], None]] = None
        self._shed_hook: Optional[Callable[[Sequence[Request]], None]] = None
        self._finish_hook: Optional[Callable[[InFlight, float], bool]] = None
        #: hedge losers awaiting release at the next scheduler boundary
        self._cancelled_rids: set = set()
        #: autoscaler rotation mask; None means "all replicas admit"
        self._enabled: Optional[List[bool]] = None

    def _res(self, r: int) -> str:
        return f"{self.res_prefix}replica{r}"

    def _name_fn(self, r: int) -> Callable[[str, object], str]:
        pre = self.res_prefix
        def fmt(kind: str, info: object) -> str:
            if kind == "prefill":
                return f"prefill/{pre}r{r}/{'+'.join(str(i) for i in info)}"
            if isinstance(info, tuple):          # fused decode leap
                return f"decode/{pre}r{r}/b{info[0]}x{info[1]}"
            return f"decode/{pre}r{r}/b{info}"
        return fmt

    def _phase_handler(self, replica: ReplicaState):
        return lambda now: self._finish_phase(replica, now)

    def _decode_handler(self, replica: ReplicaState):
        return lambda now: self._finish_decode(replica, now)

    # ---- phase submission: ServiceLane express path or task-graph mode --

    def _template(self, idx: int, kind: str) -> GraphTemplate:
        tpl = self._templates.get((idx, kind))
        if tpl is None:
            c = self._chunks[kind]
            res = self._res(idx)
            kv = res + ":kv"
            tasks = []
            for i in range(c):
                tasks.append(Task(2 * i, f"{kind}/r{idx}/c{i}", res, res,
                                  0.0, deps=(2 * i - 2,) if i else (),
                                  kind=kind))
                tasks.append(Task(2 * i + 1, f"{kind}/r{idx}/kv{i}", kv, kv,
                                  0.0, deps=(2 * i,), kind="dma"))
            tpl = GraphTemplate(tasks, tail=2 * c - 2)
            self._templates[(idx, kind)] = tpl
        return tpl

    def _phase_durs(self, kind: str, dur: float) -> List[float]:
        """Per-task durations (compute chunk, KV DMA, ...) for one phase
        of total duration ``dur`` — compiled-graph profile shares when the
        cost model carries them, else the synthetic equal split."""
        profile = self._profiles[kind]
        c = self._chunks[kind]
        durs = [0.0] * (2 * c)
        if profile is None:
            if c == 1:
                chunk_durs = [dur]
            else:
                d = dur / c
                chunk_durs = [d] * (c - 1)
                chunk_durs.append(dur - d * (c - 1))
        else:
            chunk_durs, dma_durs = profile.chunk_durations(dur)
            durs[1::2] = dma_durs
        durs[0::2] = chunk_durs
        return durs

    def _burst_step_durs(self, tpl: GraphTemplate, dur: float) -> List[float]:
        """TemplateLane burst materializer callback: bursts are always
        fused decode steps, so split one step of total ``dur``."""
        return self._phase_durs("decode", dur)

    def _submit_phase(self, idx: int, dur: float,
                      handler: Callable[[float], None],
                      kind: str, info: object) -> None:
        if not self.phase_tasks:
            self._lanes[idx].submit(dur, handler, kind=kind, info=info)
            return
        durs = self._phase_durs(kind, dur)
        # Accumulate the tail end left-to-right over the chunk chain —
        # bit-identical to chained chunk completion events.
        end = self._sim.now
        for i in range(0, len(durs), 2):
            end += durs[i]
        self._lanes[idx].submit(self._template(idx, kind), durs, end,
                                handler)

    # ---- arrivals --------------------------------------------------------

    def _arrive(self, req: Request, now: float) -> None:
        self.pending.append(req)
        if self._p_queue is not None:
            self._n_queue += 1
            n = self._obs_left - 1
            if n > 0:
                self._obs_left = n
            else:
                self._obs_tick(now)
        en = self._enabled
        for replica in self.replicas:
            if not replica.busy and (en is None or en[replica.index]):
                self._kick(replica, now)
        if self.pending:
            # The arrival survived the idle replicas, so a mid-flight
            # speculative decode leap may now be wrong: the scheduler
            # could decide differently at the next step boundary.  Roll
            # each armed leap back to the first boundary at/after now.
            for idx, leap in enumerate(self._leap):
                if leap is not None:
                    self._rollback_leap(idx, leap, now)

    def _rollback_leap(self, idx: int,
                       leap: Tuple[List[float], int], now: float) -> None:
        """Truncate a speculative decode leap at the first per-step
        boundary >= ``now``: the steps before it ran exactly as fused
        (the ``decode_stable`` contract — nothing the policy looks at
        changed), and from the truncated end the normal finish/kick path
        replays the policy's real decisions per step."""
        self._leap[idx] = None
        bounds, n = leap
        j = bisect_left(bounds, now)
        if j >= len(bounds) - 1:
            return            # lands in the final step: the leap was exact
        k = j + 1
        self._decode_k[idx] = k
        self._lanes[idx].truncate(bounds[j], info=n if k == 1 else (n, k))
        fb = self._fault_bounds[idx]
        if fb is not None:
            # the truncated leap keeps only k steps; a later crash must
            # not commit tokens for the steps the rollback discarded
            self._fault_bounds[idx] = (fb[0][:k], fb[1])
        if self._p_rollbacks is not None:
            self._n_rollbacks += 1

    def _schedule_arrival(self, req: Request) -> None:
        if self._route_hook is not None:
            # cluster mode: follow-up arrivals (closed-loop workloads)
            # go back through the router, which picks a pool at the
            # request's arrival time and accounts cluster-level offers
            self._route_hook(req)
            return
        self._n_offered += 1
        self._sim.at(max(0.0, req.t_arrive),
                     lambda r=req: self._arrive(r, self._sim.now))

    # ---- fault injection -------------------------------------------------

    def _fail(self, idx: int) -> None:
        """Replica ``idx``'s failure window opens (a pre-scheduled DES
        event — fault events at a timestamp fire before arrivals and
        completions at the same timestamp; see ``faults``)."""
        now = self._sim.now
        faults = self._faults
        if faults.mode == "slow":
            # brownout: phases *started* in the window run slower; nothing
            # is cancelled and the replica keeps admitting
            self._speed[idx] = faults.slow_factor
            if self.probe is not None:
                self.probe.event("replica_degrade", now, replica=idx)
            return
        replica = self.replicas[idx]
        self._down[idx] = True
        self._n_fail_events += 1
        if self.probe is not None:
            self.probe.event("replica_fail", now, replica=idx)
            if self._p_failures is not None:
                n = self._obs_left - 1
                if n > 0:
                    self._obs_left = n
                else:
                    self._obs_tick(now)
        if replica.busy:
            # A crash mid-fused-decode first commits the tokens of the
            # steps whose boundary precedes it — the per-step baseline
            # already delivered them (a step ending exactly at the fault
            # time loses: fault events win the timestamp tie everywhere).
            fb = self._fault_bounds[idx]
            if fb is not None:
                bounds, n_dec = fb
                j = bisect_left(bounds, now)
                if j:
                    self._total_out_tokens += j * n_dec
            # then cancel the in-flight phase via the epoch machinery:
            # the express lane keeps the truncated span, the graph lane
            # keeps committed burst steps and drops the rest
            self._lanes[idx].cancel(now)
            replica.busy = False
        self._leap[idx] = None
        self._fault_bounds[idx] = None
        if self.record_events:
            self.events.append(("fail", idx))
        # lost in-flight requests retry (or abandon) in slot order; slots
        # free in the same order so the heap state matches the fused path
        free = self._free_slots[idx]
        for fl in replica.active:
            heappush(free, fl.slot)
            if not fl.done:         # done-but-held slots were delivered
                self._retry_or_abandon(fl.req, now)
        replica.active.clear()

    def _repair(self, idx: int) -> None:
        now = self._sim.now
        if self._faults.mode == "slow":
            self._speed[idx] = 1.0
            if self.probe is not None:
                self.probe.event("replica_recover", now, replica=idx)
            return
        self._down[idx] = False
        if self.probe is not None:
            self.probe.event("replica_repair", now, replica=idx)
        if self.record_events:
            self.events.append(("repair", idx))
        self._kick(self.replicas[idx], now)

    def _retry_or_abandon(self, req: Request, now: float) -> None:
        """Re-enqueue a crash-lost request per the retry policy, or
        abandon it (attempt budget / per-request deadline exhausted).
        All progress is lost: the retried request prefills from scratch,
        but keeps its original ``t_arrive`` so E2E spans every attempt."""
        retry = self.retry
        att = self._attempts.get(req.rid, 0) + 1
        if att >= retry.max_attempts:
            self._abandon(req, now)
            return
        self._attempts[req.rid] = att
        delay = retry.backoff * retry.backoff_factor ** (att - 1)
        if retry.jitter:
            delay *= 1.0 + retry.jitter * float(self._fault_rng.random())
        t_retry = now + delay
        if t_retry - req.t_arrive > retry.deadline:
            self._abandon(req, now)
            return
        self._n_retries += 1
        if self._p_retries is not None:
            n = self._obs_left - 1
            if n > 0:
                self._obs_left = n
            else:
                self._obs_tick(now)
        if self.record_events:
            self.events.append(("retry", req.rid, att))
        if self._retry_hook is not None:
            # cluster failover: the backoff/jitter/deadline decision (and
            # the RNG draw order) above is unchanged; only the final
            # re-enqueue is redirected through the router, which picks
            # the target pool when the retry *fires*, not here.
            self._retry_hook(req, t_retry)
            return
        self._sim.at(t_retry, lambda r=req: self._arrive(r, self._sim.now))

    def _abandon(self, req: Request, now: float) -> None:
        self._n_abandoned += 1
        if self._p_abandoned is not None:
            n = self._obs_left - 1
            if n > 0:
                self._obs_left = n
            else:
                self._obs_tick(now)
        if self.record_events:
            self.events.append(("abandon", req.rid))
        if self._abandon_hook is not None:
            self._abandon_hook(req)

    # ---- the scheduling loop --------------------------------------------

    def _kick(self, replica: ReplicaState, now: float) -> None:
        idx = replica.index
        if replica.busy or self._down[idx]:
            return
        sched = self.schedulers[idx]
        en = self._enabled
        # A drained (autoscaler-disabled) replica admits nothing but
        # finishes its in-flight batch: it consults the policy against an
        # empty queue, so every stock scheduler naturally runs the batch
        # down and then idles.
        q = self.pending if en is None or en[idx] else _EMPTY_PENDING
        action = sched.decide(replica, q, now)
        while isinstance(action, Shed):
            # graceful degradation: the scheduler dropped queued requests
            # to keep the backlog bounded; account, then re-decide
            n_dropped = len(action.reqs)
            self._n_shed += n_dropped
            sbp = self._shed_by_priority
            for req in action.reqs:
                sbp[req.priority] = sbp.get(req.priority, 0) + 1
            if self._p_shed is not None:
                self._n_queue -= n_dropped
                n = self._obs_left - 1
                if n > 0:
                    self._obs_left = n
                else:
                    self._obs_tick(now)
            if self.record_events:
                for req in action.reqs:
                    self.events.append(("shed", req.rid))
            if self._shed_hook is not None:
                self._shed_hook(action.reqs)
            action = sched.decide(replica, q, now)

        if isinstance(action, Prefill):
            self._start_prefill(replica, action, now)
        elif isinstance(action, Decode):
            self._start_decode(replica, now)
        elif isinstance(action, Wait):
            key = replica.index
            if np.isfinite(action.t) and self._wait_until.get(key) != action.t:
                self._wait_until[key] = action.t
                self._sim.at(action.t, lambda r=replica: self._wake(r))
        # None: replica stays idle until an arrival or wake-up kicks it

    def _wake(self, replica: ReplicaState) -> None:
        self._wait_until.pop(replica.index, None)
        self._kick(replica, self._sim.now)

    def _start_prefill(self, replica: ReplicaState, action: Prefill,
                       now: float) -> None:
        free = self._free_slots[replica.index]
        if len(action.reqs) > len(free):
            raise RuntimeError(
                f"scheduler {self.schedulers[replica.index].name!r} admitted "
                f"{len(action.reqs)} requests with only {len(free)} free "
                f"slots on replica{replica.index}")
        record = self.record_events
        rids = []
        for req in action.reqs:
            fl = InFlight(req=req, slot=heappop(free),
                          ctx=req.prompt_tokens, t_admit=now)
            # keep actives slot-sorted: decode iteration then matches the
            # real BatchedServer's per-slot order without re-sorting
            insort(replica.active, fl, key=_slot_of)
            rids.append(req.rid)
            if record:
                self.events.append(("admit", req.rid))
        dur = self.cost.prefill_time(action.tokens)
        f = self._speed[replica.index]
        if f != 1.0:
            dur *= f            # slow-degrade window (started-phase rule)
        replica.busy = True
        if self._p_queue is not None:
            self._n_queue -= len(action.reqs)
            n = self._obs_left - 1
            if n > 0:
                self._obs_left = n
            else:
                self._obs_tick(now)
        self._submit_phase(replica.index, dur,
                           self._phase_done[replica.index],
                           "prefill", tuple(rids))
        # This admission consumed queued requests — the other change (in
        # addition to arrivals) a decode_stable policy's mid-batch
        # decision may depend on.  Roll back sibling replicas' armed
        # speculative leaps so their next boundaries consult the policy
        # against the shrunk queue, exactly like the per-step path.
        for i, leap in enumerate(self._leap):
            if leap is not None and i != replica.index:
                self._rollback_leap(i, leap, now)

    def _start_decode(self, replica: ReplicaState, now: float) -> None:
        idx = replica.index
        sched = self.schedulers[idx]
        hold = sched.hold_finished
        # static batching pays for held (finished) slots too
        n = 0
        ctx = 0
        n_dec = 0
        k_min = 0
        for f in replica.active:
            if f.done:
                if hold:
                    n += 1
                    ctx += f.ctx
                continue
            n += 1
            ctx += f.ctx
            n_dec += 1
            rem = f.req.output_tokens - f.generated
            if k_min == 0 or rem < k_min:
                k_min = rem
        # Decode leap: until the shortest slot finishes, a steady_decode
        # policy will issue identical decode steps (admission is blocked:
        # no free slot, or hold_finished holds the batch) — fuse them into
        # one task, accumulating the exact per-step costs.  When admission
        # *is* possible, a decode_stable policy still leaps, but
        # speculatively: the per-step boundaries are kept so an arrival
        # landing mid-leap rolls the fused task back (ServiceLane
        # truncation on the express path, TemplateLane burst truncation
        # in graph mode).
        k = 1
        speculate = False
        leap_ok = k_min > 1 and not self.record_events
        blocked = hold or not self._free_slots[idx]
        if leap_ok and blocked and (sched.steady_decode
                                    or sched.decode_stable):
            # Admission impossible until a slot finishes: both contracts
            # guarantee identical decode steps, so the leap is exact with
            # no snapshot needed.
            k = k_min
        elif leap_ok and sched.decode_stable:
            # Admission possible: leap speculatively and arm rollback (an
            # arrival may change the next-step decision).
            k = k_min
            speculate = True
        # Exact per-step cost accumulation.  For the stock affine
        # ServingCostModel, decode_step_time(n, ctx) is inlined with
        # identical arithmetic (bit-for-bit, ~2x fewer ns per fused
        # step); subclasses overriding the method are honored per step.
        cost = self.cost
        affine = (type(cost).decode_step_time
                  is ServingCostModel.decode_step_time)
        f = self._speed[idx]
        # crash-faults need the step boundaries of *every* fused decode
        # (blocked leaps included): a crash mid-leap commits the steps
        # whose boundary precedes it.  Collecting bounds never changes
        # the duration arithmetic (see _leap_spans).
        faultable = (self._faults is not None
                     and self._faults.mode == "crash" and k > 1)
        if affine:
            base = cost.decode_fixed + cost.decode_per_token * n
            c_d = cost.decode_per_ctx_token
            if f != 1.0:
                # slow-degrade: scale the step coefficients (the fused
                # Monte-Carlo path applies the identical scaling, so the
                # per-step arithmetic stays bit-equal across paths)
                base *= f
                c_d *= f
            c0 = base + c_d * ctx
            dur, bounds = _leap_spans(now, c0, base, c_d, ctx, n_dec, k,
                                      speculate or faultable,
                                      self._leap_scratch)
        else:
            c0 = cost.decode_step_time(n, ctx)
            if f != 1.0:
                c0 *= f
            dur = c0
            bounds = None
            if speculate or faultable:
                bounds = [now + c0]
                for _ in range(k - 1):
                    ctx += n_dec
                    s = cost.decode_step_time(n, ctx)
                    dur += s * f if f != 1.0 else s
                    bounds.append(now + dur)
            else:
                for _ in range(k - 1):
                    ctx += n_dec
                    s = cost.decode_step_time(n, ctx)
                    dur += s * f if f != 1.0 else s
        if self.record_events:
            self.events.append(
                ("step", tuple(sorted(f.req.rid for f in replica.active
                                      if not f.done))))
        self._decode_k[idx] = k
        self._decode_tfirst[idx] = now + c0
        self._leap[idx] = (bounds, n) if speculate else None
        if faultable:
            self._fault_bounds[idx] = (bounds, n_dec)
        if self._p_leaps is not None and k > 1:
            self._n_leap_steps += k
            if speculate:
                self._n_spec += 1
        replica.busy = True
        if speculate and self.phase_tasks:
            # Graph-mode leap: K chained step instances as ONE lane entry
            # and one completion event — O(1) bookkeeping per leap; the
            # per-step `bounds` double as the rollback snapshot points.
            self._lanes[idx].submit_burst(self._template(idx, "decode"),
                                          bounds, self._decode_done[idx])
        else:
            self._submit_phase(idx, dur, self._decode_done[idx], "decode",
                               n if k == 1 else (n, k))

    def _finish_phase(self, replica: ReplicaState, now: float) -> None:
        replica.busy = False
        if self._cancelled_rids:
            self._sweep_cancelled(replica)
        self._kick(replica, now)

    def _sweep_cancelled(self, replica: ReplicaState) -> None:
        """Release hedge-cancelled requests at a prefill boundary: they
        leave the batch and free their slots without ever decoding.
        (Decode boundaries release through ``_finish_decode``'s finished
        path instead, which preserves hold-finished batch semantics.)"""
        cr = self._cancelled_rids
        free = self._free_slots[replica.index]
        kept = []
        changed = False
        for fl in replica.active:
            if not fl.done and fl.req.rid in cr:
                heappush(free, fl.slot)
                cr.discard(fl.req.rid)
                changed = True
            else:
                kept.append(fl)
        if changed:
            replica.active[:] = kept

    def _finish_decode(self, replica: ReplicaState, now: float) -> None:
        idx = replica.index
        self._leap[idx] = None
        self._fault_bounds[idx] = None
        sched = self.schedulers[idx]
        k = self._decode_k[idx]
        t_first = self._decode_tfirst[idx]
        finished: List[InFlight] = []
        decoding_left = 0
        tokens = 0
        # actives are slot-sorted, mirroring the real BatchedServer's
        # finish ordering
        cr = self._cancelled_rids or None
        for fl in replica.active:
            if fl.done:
                continue
            fl.generated += k
            fl.ctx += k
            tokens += k
            if fl.t_first is None:
                fl.t_first = t_first
            if fl.generated >= fl.req.output_tokens:
                fl.done = True
                finished.append(fl)
            elif cr is not None and fl.req.rid in cr:
                # hedge loser: leaves the batch at this step boundary —
                # the same instant in every mode, so leap-vs-per-step
                # parity holds under cancellation
                fl.done = True
                finished.append(fl)
            else:
                decoding_left += 1
        self._total_out_tokens += tokens
        release = finished
        if sched.hold_finished:
            # the batch drains only when every member is done
            release = [] if decoding_left else list(replica.active)
        free = self._free_slots[replica.index]
        for fl in release:
            replica.active.remove(fl)
            heappush(free, fl.slot)
        fh = self._finish_hook
        n_rec = 0
        for fl in finished:
            if fh is not None and not fh(fl, now):
                continue     # swallowed: a hedge duplicate already won
            n_rec += 1
            if self.record_events:
                self.events.append(("finish", fl.req.rid))
            self.lane_state.record(
                fl.req.rid, replica.index, fl.slot, fl.req.t_arrive,
                fl.t_admit, fl.t_first, now, fl.req.prompt_tokens,
                fl.req.output_tokens)
            follow = self.workload.on_complete(fl.req, now)
            if follow is not None:
                self._schedule_arrival(follow)
        if self._p_completed is not None:
            self._n_completed += n_rec
            n = self._obs_left - 1
            if n > 0:
                self._obs_left = n
            else:
                self._obs_tick(now)
        replica.busy = False
        self._kick(replica, now)

    # ---- cluster support -------------------------------------------------

    def cancel_request(self, rid: int, now: float) -> str:
        """Withdraw ``rid`` from this pool (a hedge duplicate lost the
        race on another pool).  A queued copy leaves immediately; an
        admitted copy is marked and released at its replica's next
        scheduler boundary — a prefill end or a decode step boundary,
        which fall at the same instants whether or not decode steps are
        fused, so leap-vs-per-step parity survives cancellation.  An armed
        speculative decode leap is rolled back first so that boundary
        arrives at per-step fidelity instead of the leap's far end.
        Returns ``"queued"`` / ``"inflight"`` / ``"absent"``."""
        pending = self.pending
        for i, req in enumerate(pending):
            if req.rid == rid:
                del pending[i]
                if self._p_queue is not None:
                    self._n_queue -= 1
                    n = self._obs_left - 1
                    if n > 0:
                        self._obs_left = n
                    else:
                        self._obs_tick(now)
                return "queued"
        for replica in self.replicas:
            for fl in replica.active:
                if fl.req.rid == rid and not fl.done:
                    self._cancelled_rids.add(rid)
                    idx = replica.index
                    leap = self._leap[idx]
                    if leap is not None:
                        self._rollback_leap(idx, leap, now)
                    return "inflight"
        return "absent"

    def set_replica_enabled(self, idx: int, enabled: bool,
                            now: float) -> None:
        """Autoscaler support: a disabled replica admits nothing (its
        scheduler sees an empty queue) but drains in-flight work
        naturally; re-enabling kicks it against the real queue."""
        en = self._enabled
        if en is None:
            en = self._enabled = [True] * len(self.replicas)
        if en[idx] == enabled:
            return
        en[idx] = enabled
        if enabled:
            self._kick(self.replicas[idx], now)

    def n_enabled(self) -> int:
        en = self._enabled
        return len(self.replicas) if en is None else sum(en)

    # ---- observability ---------------------------------------------------

    def _obs_tick(self, now: float) -> None:
        """Append one aligned sample to every serving track from the
        plain-int accumulators the hot sites bump.  Runs every
        ``probe.sample_every``-th instrumented event (and once at the end
        of the run), so handles/series see raw appends — the site
        countdown IS the decimation layer for serving metrics."""
        self._obs_left = self._obs_every
        for h, v in ((self._p_queue, self._n_queue),
                     (self._p_completed, self._n_completed),
                     (self._p_leaps, self._n_leap_steps),
                     (self._p_spec, self._n_spec),
                     (self._p_rollbacks, self._n_rollbacks),
                     (self._p_failures, self._n_fail_events),
                     (self._p_retries, self._n_retries),
                     (self._p_abandoned, self._n_abandoned),
                     (self._p_shed, self._n_shed)):
            h.value = v = float(v)
            h.series._append(now, v)
        for r, h in zip(self.replicas, self._p_occ):
            h.value = v = float(len(r.active))
            h.series._append(now, v)

    # ---- entry point -----------------------------------------------------

    def _arm_faults(self) -> None:
        """Schedule this pool's compiled fault events on the engine.
        Called before any arrival is scheduled — fault events at a tied
        timestamp must beat arrivals/completions on the heap's sequence
        tie-break (the cluster arms every pool first, then routes)."""
        faults = self._faults
        if faults is not None:
            # Fault events are scheduled FIRST, in schedule order (sorted
            # by time, repairs before failures at equal times), so at any
            # tied timestamp they beat arrivals — and every runtime event
            # (completions, retries) — on the heap's sequence tie-break.
            # The fused Monte-Carlo loop mirrors this priority exactly.
            for t, code, r in faults.events:
                if code:
                    self._sim.at(t, lambda i=r: self._fail(i))
                else:
                    self._sim.at(t, lambda i=r: self._repair(i))

    def run(self) -> ServingReport:
        self._arm_faults()
        for req in self.workload.initial():
            self._schedule_arrival(req)
        sim_result = self._sim.run()
        return self._build_report(sim_result)

    def _build_report(self, sim_result: SimResult,
                      flush: bool = True) -> ServingReport:
        faults = self._faults
        util = 0.0
        if sim_result.makespan > 0:
            util = sum(
                sim_result.resource_busy.get(self._res(r.index), 0.0)
                for r in self.replicas
            ) / (len(self.replicas) * sim_result.makespan)

        probe = self.probe
        if probe is not None:
            # close the counter tracks at the makespan so they span the
            # whole run, and record the end-of-run utilization level
            # (fault events past the last completion may extend the span)
            end_t = max(sim_result.makespan, self._sim.now)
            self._obs_tick(end_t)
            probe.gauge(f"{self._obs_ns}/replica_util",
                        unit="frac").set(end_t, util)
            if flush:
                probe.flush()

        ls = self.lane_state
        ls.sort_by_rid()
        ttft, tpot, e2e, queue_delay = ls.stats()
        mk = sim_result.makespan
        return ServingReport(
            workload=self.workload.name,
            scheduler=self.schedulers[0].name,
            cost_model=self.cost.name,
            replicas=len(self.replicas), slots=self.slots,
            n_requests=ls.n,
            duration=mk,
            output_tokens=self._total_out_tokens,
            ttft=ttft, tpot=tpot, e2e=e2e, queue_delay=queue_delay,
            replica_util=util,
            requests=_LazyRequests(ls),
            sim_result=sim_result,
            events=self.events,
            n_offered=self._n_offered,
            n_failures=(faults.n_failures(mk) if faults is not None else 0),
            n_retries=self._n_retries,
            n_abandoned=self._n_abandoned,
            n_shed=self._n_shed,
            shed_by_priority=dict(self._shed_by_priority),
            availability=(faults.availability(mk, len(self.replicas))
                          if faults is not None else 1.0))


def simulate_serving(cost: ServingCostModel,
                     scheduler_factory: Callable[[], BatchScheduler],
                     workload: Workload, replicas: int = 1, slots: int = 8,
                     record_events: bool = False,
                     phase_tasks: int = 0,
                     probe=None, failures=None,
                     retry: Optional[RetryPolicy] = None,
                     fault_seed=None) -> ServingReport:
    """One-shot convenience wrapper around :class:`ServingSimulator`."""
    return ServingSimulator(cost, scheduler_factory, workload,
                            replicas=replicas, slots=slots,
                            record_events=record_events,
                            phase_tasks=phase_tasks,
                            probe=probe, failures=failures, retry=retry,
                            fault_seed=fault_seed).run()
