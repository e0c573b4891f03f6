"""Discrete-event simulation engine (the SystemC / Platform Architect analog).

Executes a hardware-adapted task graph on named resources while preserving
causality — the property the paper argues distinguishes simulation from
statistical estimation: a DMA that a compute task depends on *blocks* it,
and transfers sharing a link contend for its bandwidth.

Resources come in two flavours (:class:`ResourceSpec`):

  * ``fifo``   — a ``servers``-wide FIFO station: up to ``servers`` tasks
    run concurrently, each at full rate; excess tasks queue in ready order
    (tie-broken by task id for determinism).  A single-server FIFO is the
    classic exclusive resource.
  * ``shared`` — a bandwidth-shared channel (generalized processor
    sharing): every admitted task progresses at rate
    ``min(1, servers / n_active)``, so total throughput never exceeds
    ``servers`` times the annotated full rate.  Two collectives sharing an
    ICI link each see half the bandwidth instead of strictly serializing.

Task durations are pre-annotated at *full rate* by the virtual hardware
models (repro.core.taskgraph.compiler); contention stretches them.
Unknown resources default to a single-server FIFO, so plain task lists
behave exactly as the original exclusive-resource engine.

The module holds two engines, one per job:

  * :func:`simulate_static` runs a *static* task graph (no callbacks, no
    injection) over precomputed dependency arrays (:class:`StaticCache`)
    with deferred record materialization — the estimator's DES backend;
  * :class:`DynamicSimulator` runs everything that injects work while the
    simulation runs — the foundation of the traffic-driven serving
    simulator (``repro.serve_sim``):

      - :meth:`DynamicSimulator.at` schedules a timed callback (e.g. a
        request arrival) that runs inside the event loop and may inject
        new work;
      - :meth:`DynamicSimulator.inject` adds a task *while the simulation
        runs*; its dependencies may already be satisfied or still in
        flight;
      - ``on_complete`` observers fire as tasks finish, letting a
        scheduler react causally (free a slot, admit the next request,
        issue the next decode step);
      - :meth:`DynamicSimulator.lane` opens a :class:`ServiceLane` — the
        express path for the dominant serving pattern (one task at a time
        on a dedicated single-server resource, submitted only when idle)
        that skips Task construction and dependency bookkeeping entirely —
        and :meth:`DynamicSimulator.template_lane` its graph-structured
        sibling, :class:`TemplateLane`.

Both engines share the causal semantics and tie-breaking rules, and both
are held to the frozen seed engine (``tests/reference_engine.py``) by the
golden parity tests in ``tests/test_engine_parity.py``.

Complexity: shared-link contention is O(log n) per event via virtual-time
generalized processor sharing — each admitted task gets a fixed virtual
finish time, completions pop from a heap, and real-to-virtual conversion
happens only at rate-change boundaries.  (The seed engine decremented
every active task's remaining work on every event: O(n) per event,
O(n^2) per burst of n concurrent transfers.)
"""
from __future__ import annotations

import heapq
import math
from bisect import bisect_left
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class ResourceSpec:
    """How a named resource serves tasks."""

    name: str
    servers: int = 1
    mode: str = "fifo"           # fifo | shared

    def __post_init__(self):
        if self.servers < 1:
            raise ValueError(f"resource {self.name}: servers must be >= 1")
        if self.mode not in ("fifo", "shared"):
            raise ValueError(f"resource {self.name}: unknown mode {self.mode}")


@dataclass(slots=True)
class Task:
    tid: int
    name: str
    layer: str                  # grouping key for per-layer stats
    resource: str               # e.g. "nce", "dma", "ici_model"
    duration: float             # seconds at full rate
    deps: Tuple[int, ...] = ()
    kind: str = "compute"       # compute | dma | collective | launch | host
    nbytes: int = 0
    flops: int = 0
    op_id: int = -1             # index of the originating LayerOp (-1: none)
    anno: Optional[object] = None   # RateAnno re-annotation rule (what-if)


@dataclass(slots=True)
class TaskRecord:
    task: Task
    start: float
    end: float


class SimResult:
    """Outcome of one simulation run.

    ``records`` may be materialized lazily: the static fast path and the
    serving lanes keep start/end arrays and only build ``TaskRecord``
    objects when a trace/Gantt export actually reads them.
    """

    __slots__ = ("makespan", "resource_busy", "layer_time", "_records",
                 "_records_thunk")

    def __init__(self, makespan: float,
                 records: Optional[List[TaskRecord]] = None,
                 resource_busy: Optional[Dict[str, float]] = None,
                 layer_time: Optional[Dict[str, Tuple[float, float]]] = None,
                 records_thunk: Optional[Callable[[], List[TaskRecord]]] = None):
        self.makespan = makespan
        self.resource_busy = resource_busy if resource_busy is not None else {}
        self.layer_time = layer_time if layer_time is not None else {}
        self._records = records
        self._records_thunk = records_thunk

    @property
    def records(self) -> List[TaskRecord]:
        if self._records is None:
            thunk = self._records_thunk
            self._records = thunk() if thunk is not None else []
            self._records_thunk = None
        return self._records

    def utilization(self, resource: str) -> float:
        return (self.resource_busy.get(resource, 0.0) / self.makespan
                if self.makespan > 0 else 0.0)

    def layer_durations(self) -> Dict[str, float]:
        return {k: e - s for k, (s, e) in self.layer_time.items()}

    def __repr__(self) -> str:
        n = "lazy" if self._records is None else len(self._records)
        return (f"SimResult(makespan={self.makespan!r}, "
                f"n_records={n}, "
                f"resources={sorted(self.resource_busy)})")


#: Shared-channel near-tie completion tolerance, *relative* to each task's
#: own full-rate duration: when a channel's head task completes, tasks whose
#: virtual finish lies within ``_REL_EPS * work`` of it complete with it.
#: (The seed engine used an absolute 1e-15 s cutoff, which completed
#: genuinely unfinished tasks early whenever durations were themselves
#: O(1e-15).)
_REL_EPS = 1e-12


class ServiceLane:
    """Express path for dynamic service on one single-server FIFO resource.

    The traffic-driven serving simulator issues one prefill/decode task at
    a time per replica, always from an idle state — so the general
    inject/enqueue/drain machinery (Task construction, dependency and
    duration arrays, ready queues) is pure overhead.  A lane keeps plain
    start/end/kind arrays, schedules the completion event directly, and
    materializes ``TaskRecord``s lazily only when a trace is requested.

    ``name_fn(kind, info) -> str`` builds record names at materialization
    time, so per-step f-string formatting is also deferred.
    """

    __slots__ = ("sim", "resource", "busy", "busy_time", "starts", "ends",
                 "kinds", "infos", "name_fn", "epoch", "_handler")

    def __init__(self, sim: "DynamicSimulator", resource: str,
                 name_fn: Optional[Callable[[str, object], str]] = None):
        self.sim = sim
        self.resource = resource
        self.busy = False
        self.busy_time = 0.0
        self.starts: List[float] = []
        self.ends: List[float] = []
        self.kinds: List[str] = []
        self.infos: List[object] = []
        self.name_fn = name_fn
        # ``epoch`` invalidates the scheduled completion of a task whose
        # end moved (speculative decode-leap rollback, :meth:`truncate`).
        self.epoch = 0
        self._handler: Optional[Callable[[float], None]] = None

    def submit(self, duration: float, handler: Callable[[float], None],
               kind: str = "task", info: object = None) -> None:
        """Start a task now; ``handler(now)`` runs when it completes."""
        if self.busy:
            raise RuntimeError(f"lane {self.resource!r} is busy")
        sim = self.sim
        self.busy = True
        start = sim._now
        end = start + duration
        self.starts.append(start)
        self.ends.append(end)
        self.kinds.append(kind)
        self.infos.append(info)
        self.busy_time += duration
        self._handler = handler
        sim._seq += 1
        heapq.heappush(sim._events,
                       (end, sim._seq, "lane", (self, handler, self.epoch)))

    def truncate(self, new_end: float, info: object = None) -> None:
        """Shorten the in-flight task to end at ``new_end``.

        The speculative decode-leap submits a fused task optimistically
        and rolls it back to a step boundary when the scheduler must be
        consulted earlier (an arrival landed mid-leap): the recorded span
        shrinks, the stale completion event is invalidated via ``epoch``,
        and the completion is rescheduled at the truncated end.
        """
        if not self.busy:
            raise RuntimeError(f"lane {self.resource!r} has no task to "
                               f"truncate")
        old_end = self.ends[-1]
        if new_end >= old_end:
            return
        if new_end < self.starts[-1]:
            raise ValueError(f"cannot truncate before the task start "
                             f"({new_end} < {self.starts[-1]})")
        self.ends[-1] = new_end
        self.busy_time -= old_end - new_end
        if info is not None:
            self.infos[-1] = info
        self.epoch += 1
        sim = self.sim
        sim._seq += 1
        heapq.heappush(
            sim._events,
            (new_end, sim._seq, "lane", (self, self._handler, self.epoch)))

    def cancel(self, new_end: float, info: object = None) -> None:
        """Abort the in-flight task at ``new_end`` (a replica crash).

        Like :meth:`truncate`, the recorded span shrinks to the abort
        time and the stale completion event is invalidated via ``epoch``
        — but no completion is rescheduled and the handler never fires:
        the lane simply goes idle.  The partial span stays recorded
        (work the replica really did before dying)."""
        if not self.busy:
            raise RuntimeError(f"lane {self.resource!r} has no task to "
                               f"cancel")
        old_end = self.ends[-1]
        if new_end < self.starts[-1]:
            raise ValueError(f"cannot cancel before the task start "
                             f"({new_end} < {self.starts[-1]})")
        if new_end < old_end:
            self.ends[-1] = new_end
            self.busy_time -= old_end - new_end
        if info is not None:
            self.infos[-1] = info
        self.epoch += 1
        self.busy = False
        self._handler = None

    def _nonempty(self) -> bool:
        return bool(self.starts)

    def _merge(self, resource_busy: Dict[str, float],
               layer_time: Dict[str, Tuple[float, float]]) -> float:
        """Fold this lane's busy time and layer span into the run-level
        aggregates; returns the lane's makespan contribution."""
        res = self.resource
        resource_busy[res] = resource_busy.get(res, 0.0) + self.busy_time
        span = (self.starts[0], self.ends[-1])
        cur = layer_time.get(res)
        if cur is not None:
            span = (min(cur[0], span[0]), max(cur[1], span[1]))
        layer_time[res] = span
        return self.ends[-1]

    def _materialize(self, tid0: int) -> List[TaskRecord]:
        name_fn = self.name_fn
        res = self.resource
        out = []
        for i, (s, e, k, info) in enumerate(zip(self.starts, self.ends,
                                                self.kinds, self.infos)):
            name = name_fn(k, info) if name_fn is not None else f"{res}/{k}"
            out.append(TaskRecord(
                Task(tid=tid0 + i, name=name, layer=res, resource=res,
                     duration=e - s, kind=k), s, e))
        return out


class TemplateLane:
    """Graph-structured service lane: full per-task template records at
    ServiceLane speed.

    The serving simulator's task-graph mode submits one phase template
    instance (chunked compute with KV/DMA sidecars) per scheduler
    decision.  Running each chunk through the engine's event loop costs
    O(chunks) heap events per phase — the entire gap between graph mode
    and the express :class:`ServiceLane`.  But a phase template on
    *dedicated* single-server FIFO resources is deterministic at
    submission time: chunk chains serialize on the phase resource, and
    sidecar tasks (KV writes) serialize in template order on theirs.  So
    a TemplateLane schedules exactly **one completion event per phase**
    (at the precomputed tail end) and stores the phase as a compact
    entry; the full per-task schedule — including real DMA/compute
    overlap across chunks and phases — is replayed lazily when the run's
    aggregates or ``TaskRecord``s are read.

    Speculative decode-leap support (the GraphTemplate epoch-snapshot
    mechanism): :meth:`submit_burst` books ``K`` chained step instances
    as one entry whose per-step boundary times are the snapshot points,
    and :meth:`truncate` rolls the burst back to a boundary — the stale
    completion event is invalidated via ``epoch`` exactly like
    :meth:`ServiceLane.truncate`, and the tasks of every step after the
    boundary are dropped before they ever materialize.

    Contract (validated once per template): template tasks are
    topologically ordered by local id, every task's resources are
    dedicated to this lane, and the tail's dependency closure determines
    the phase end (the caller precomputes it with the same left-to-right
    chunk accumulation that chained completion events produce, so the
    replay equals an event-by-event run of the same tasks bit for bit).
    """

    __slots__ = ("sim", "resource", "busy", "epoch", "entries", "end",
                 "step_durs", "_handler", "_fin", "_sched", "_checked",
                 "_prev_end")

    def __init__(self, sim: "DynamicSimulator", resource: str,
                 step_durs: Optional[Callable] = None):
        """``step_durs(tpl, dur) -> per-task durations`` splits one burst
        step's total duration at materialization time (bursts store only
        their boundary times)."""
        self.sim = sim
        self.resource = resource
        self.busy = False
        self.epoch = 0
        #: (template, t0, per-task durations | None, burst bounds | None)
        self.entries: List[Tuple] = []
        self.end = 0.0
        self._prev_end = 0.0     # lane end excluding the in-flight entry
        self.step_durs = step_durs
        self._handler: Optional[Callable[[float], None]] = None
        self._fin = None
        self._sched = None
        #: template id -> (compute_res, sidecar_res) | None (see _chain_key)
        self._checked: Dict[int, Optional[Tuple[str, str]]] = {}

    def _check(self, tpl: GraphTemplate) -> None:
        if id(tpl) in self._checked:
            return
        for i, dd in enumerate(tpl.deps):
            for d in dd:
                if d >= i:
                    raise ValueError(
                        "TemplateLane templates must be topologically "
                        f"ordered by local id (task {i} depends on {d})")
        self._checked[id(tpl)] = self._chain_key(tpl)

    def submit(self, tpl: GraphTemplate, durations: Sequence[float],
               end: float, handler: Callable[[float], None]) -> None:
        """Start one instance of ``tpl`` now; ``end`` is the precomputed
        absolute completion time of its tail and ``handler(now)`` runs
        there."""
        if self.busy:
            raise RuntimeError(f"template lane {self.resource!r} is busy")
        self._check(tpl)
        sim = self.sim
        self._fin = self._sched = None
        self.entries.append((tpl, sim._now, durations, None))
        self._prev_end = self.end
        self.end = end
        self.busy = True
        self._handler = handler
        sim._seq += 1
        heapq.heappush(sim._events,
                       (end, sim._seq, "lane", (self, handler, self.epoch)))

    def submit_burst(self, tpl: GraphTemplate, bounds,
                     handler: Callable[[float], None]) -> None:
        """Start ``len(bounds)`` chained step instances of ``tpl`` as one
        entry — the speculative decode leap in graph mode.  ``bounds``
        are the absolute per-step boundary (snapshot) times; step ``i``
        spans ``(bounds[i-1], bounds[i]]`` and its per-task durations are
        recovered at materialization via ``step_durs``.  One completion
        event is scheduled at ``bounds[-1]``; ``handler`` fires there (or
        at the truncated boundary after a rollback)."""
        if self.busy:
            raise RuntimeError(f"template lane {self.resource!r} is busy")
        self._check(tpl)
        sim = self.sim
        self._fin = self._sched = None
        self.entries.append((tpl, sim._now, None, bounds))
        self._prev_end = self.end
        self.end = end = float(bounds[-1])
        self.busy = True
        self._handler = handler
        sim._seq += 1
        heapq.heappush(sim._events,
                       (end, sim._seq, "lane", (self, handler, self.epoch)))

    def truncate(self, new_end: float, info: object = None) -> None:
        """Roll the in-flight burst back to the snapshot boundary at
        ``new_end``: the steps before it ran exactly as fused, the steps
        after it are invalidated before they materialize, and the stale
        completion event is superseded via ``epoch`` (mirroring
        :meth:`ServiceLane.truncate`).  ``info`` is accepted for
        signature compatibility with the express lane (template records
        carry their own structure)."""
        if not self.busy:
            raise RuntimeError(f"template lane {self.resource!r} has no "
                               f"task to truncate")
        if new_end >= self.end:
            return
        tpl, t0, durs, bounds = self.entries[-1]
        if bounds is None:
            raise RuntimeError("only burst submissions can be truncated")
        j = bisect_left(bounds, new_end)
        if j >= len(bounds) - 1:
            return
        self._fin = self._sched = None
        self.entries[-1] = (tpl, t0, None, bounds[:j + 1])
        self.end = end = float(bounds[j])
        self.epoch += 1
        sim = self.sim
        sim._seq += 1
        heapq.heappush(
            sim._events,
            (end, sim._seq, "lane", (self, self._handler, self.epoch)))

    def cancel(self, new_end: float, info: object = None) -> None:
        """Abort the in-flight phase or burst (a replica crash).

        A burst keeps the steps whose boundary precedes ``new_end`` —
        they ran exactly as the per-step baseline would have run them —
        and drops the rest; a plain phase entry is dropped whole before
        it materializes (template entries are step-granular at best, so
        graph mode records no partial-step work — the express
        :class:`ServiceLane` keeps the truncated span instead, so the
        two serving modes agree on request metrics under faults, not on
        task records).  The stale completion event is
        invalidated via ``epoch`` and the lane goes idle."""
        if not self.busy:
            raise RuntimeError(f"template lane {self.resource!r} has no "
                               f"task to cancel")
        self._fin = self._sched = None
        tpl, t0, durs, bounds = self.entries[-1]
        j = bisect_left(bounds, new_end) if bounds is not None else 0
        if j >= 1:
            self.entries[-1] = (tpl, t0, None, bounds[:j])
            self.end = float(bounds[j - 1])
        else:
            self.entries.pop()
            self.end = self._prev_end
        self.epoch += 1
        self.busy = False
        self._handler = None

    # ---- lazy schedule replay -------------------------------------------

    def _run_instance(self, tpl: GraphTemplate, t0: float,
                      durs: Sequence[float], starts: List[float],
                      ends: List[float], free: Dict[str, float],
                      busy: Dict[str, float],
                      lay: Dict[str, List[float]]) -> float:
        """Schedule one instance: template order is the dispatch order on
        each (dedicated, single-server FIFO) resource, so every start is
        ``max(dep ends, resource free)``.  Returns the max end."""
        deps = tpl.deps
        res_of = tpl.res_of
        lay_of = tpl.layer_of
        res_names = tpl.res_names
        lay_names = tpl.layer_names
        base = len(ends)
        mk = t0
        for i in range(tpl.n):
            ready = t0
            for d in deps[i]:
                e = ends[base + d]
                if e > ready:
                    ready = e
            rn = res_names[res_of[i]]
            rf = free.get(rn, 0.0)
            start = ready if ready > rf else rf
            dur = durs[i]
            end = start + dur
            free[rn] = end
            starts.append(start)
            ends.append(end)
            busy[rn] = busy.get(rn, 0.0) + dur
            name = lay_names[lay_of[i]]
            span = lay.get(name)
            if span is None:
                lay[name] = [start, end]
            else:
                if start < span[0]:
                    span[0] = start
                if end > span[1]:
                    span[1] = end
            if end > mk:
                mk = end
        return mk

    def _chain_key(self, tpl: GraphTemplate):
        """(compute_res, sidecar_res) if ``tpl`` is the serving chunk
        chain + sidecar shape — compute chunks 0,2,4,... chained on one
        resource, each feeding a sidecar task on a second — else None.
        The shape admits closed-form aggregates: the compute chain is a
        pure cumulative sum from ``t0`` and the sidecar serializes in
        chunk order, so :meth:`_finalize` runs O(chunks) float ops per
        instance with no per-task dict lookups."""
        n = tpl.n
        if (n < 2 or n % 2 or len(tpl.res_names) != 2
                or tpl.tail != n - 2
                or tpl.layer_names != tpl.res_names
                or tpl.layer_of != tpl.res_of):
            return None
        for i in range(0, n, 2):
            if (tpl.res_of[i] != 0 or tpl.res_of[i + 1] != 1
                    or tpl.deps[i] != ((i - 2,) if i else ())
                    or tpl.deps[i + 1] != (i,)):
                return None
        return tpl.res_names[0], tpl.res_names[1]

    def _agg_chain(self, key: Tuple[str, str]):
        """Closed-form aggregates for all-chain entries: one pass over
        chunk durations, no per-task schedule arrays."""
        r0, r1 = key
        comp_busy = 0.0
        dma_busy = 0.0
        kvf = 0.0
        kv_first = None
        end = t0_first = self.entries[0][1]
        step_durs = self.step_durs
        for tpl, t0, durs, bounds in self.entries:
            if bounds is None:
                spans = ((t0, durs),)
            else:
                prev = t0
                spans = []
                for b in bounds:
                    b = float(b)
                    spans.append((prev, step_durs(tpl, b - prev)))
                    prev = b
            for s0, dd in spans:
                e = s0
                for i in range(0, len(dd), 2):
                    d = dd[i]
                    e += d
                    comp_busy += d   # per-chunk, matching the event
                    dk = dd[i + 1]   # loop's per-task accumulation
                    s = e if e > kvf else kvf
                    if kv_first is None:
                        kv_first = s
                    kvf = s + dk
                    dma_busy += dk
                end = e
        busy = {r0: comp_busy, r1: dma_busy}
        lay = {r0: [t0_first, end]}
        if kv_first is not None:
            lay[r1] = [kv_first, kvf]
        return busy, lay, end if end > kvf else kvf

    def _finalize(self):
        """Cached run-level aggregates: (resource busy, layer spans,
        makespan).  Chain-shaped lanes take the closed-form path; the
        generic path replays the full schedule (and caches it for
        :meth:`_schedule`)."""
        fin = self._fin
        if fin is None:
            checked = self._checked
            key = chain = checked[id(self.entries[0][0])]
            if chain is not None:
                for tpl, _, _, _ in self.entries:
                    if checked[id(tpl)] != key:
                        chain = None
                        break
            if chain is not None:
                busy, lay, mk = self._agg_chain(chain)
            else:
                starts, ends, busy, lay, mk = self._replay()
                self._sched = (starts, ends)
            fin = self._fin = (busy, lay, mk)
        return fin

    def _replay(self):
        """Full generic schedule replay over every entry."""
        starts: List[float] = []
        ends: List[float] = []
        free: Dict[str, float] = {}
        busy: Dict[str, float] = {}
        lay: Dict[str, List[float]] = {}
        mk = 0.0
        run = self._run_instance
        step_durs = self.step_durs
        for tpl, t0, durs, bounds in self.entries:
            if bounds is None:
                e = run(tpl, t0, durs, starts, ends, free, busy, lay)
            else:
                prev = t0
                e = t0
                for b in bounds:
                    b = float(b)
                    e = run(tpl, prev, step_durs(tpl, b - prev),
                            starts, ends, free, busy, lay)
                    prev = b
            if e > mk:
                mk = e
        return starts, ends, busy, lay, mk

    def _schedule(self):
        """Cached per-task (starts, ends) — the records path; computed on
        demand so aggregate-only runs never pay the per-task replay."""
        sched = self._sched
        if sched is None:
            starts, ends, _, _, _ = self._replay()
            sched = self._sched = (starts, ends)
        return sched

    def _nonempty(self) -> bool:
        return bool(self.entries)

    def _merge(self, resource_busy: Dict[str, float],
               layer_time: Dict[str, Tuple[float, float]]) -> float:
        busy, lay, mk = self._finalize()
        for rn, b in busy.items():
            resource_busy[rn] = resource_busy.get(rn, 0.0) + b
        for name, (s, e) in lay.items():
            cur = layer_time.get(name)
            if cur is not None:
                s, e = min(cur[0], s), max(cur[1], e)
            layer_time[name] = (s, e)
        return mk if mk > self.end else self.end

    def _materialize(self, tid0: int) -> List[TaskRecord]:
        starts, ends = self._schedule()
        out = []
        k = 0
        tid = tid0
        for tpl, t0, durs, bounds in self.entries:
            reps = 1 if bounds is None else len(bounds)
            names, kinds = tpl.names, tpl.kinds
            res_names, lay_names = tpl.res_names, tpl.layer_names
            res_of, lay_of = tpl.res_of, tpl.layer_of
            nbytes, flops = tpl.nbytes, tpl.flops
            deps = tpl.deps
            n = tpl.n
            tail = tpl.tail
            for r in range(reps):
                base = tid
                for i in range(n):
                    dd = tuple(base + d for d in deps[i])
                    if r and not dd:
                        # burst steps chain: this step's roots follow the
                        # previous step's tail
                        dd = (base - n + tail,)
                    s = starts[k]
                    e = ends[k]
                    out.append(TaskRecord(
                        Task(tid=tid, name=names[i],
                             layer=lay_names[lay_of[i]],
                             resource=res_names[res_of[i]],
                             duration=e - s, deps=dd, kind=kinds[i],
                             nbytes=nbytes[i], flops=flops[i]), s, e))
                    tid += 1
                    k += 1
        return out


# ---------------------------------------------------------------------------
# Array-backed fast path for static graphs
# ---------------------------------------------------------------------------


class StaticCache:
    """Precomputed dependency/resource structure for one static task list.

    System-independent: resource *names*, the dependency CSR, and layer
    grouping depend only on the task list, so a cache built once per
    compiled graph is shared across every re-annotated what-if variant
    (``CompiledGraph.sim_cache()``).  Per-system resource widths/modes and
    the duration vector are passed to :func:`simulate_static` per run.
    """

    __slots__ = ("n", "index_of", "tids", "dependents", "indeg", "res_of",
                 "res_names", "layer_of", "layer_names")

    def __init__(self, tasks: Sequence[Task]):
        n = len(tasks)
        self.n = n
        self.tids = [t.tid for t in tasks]
        index_of = {t.tid: i for i, t in enumerate(tasks)}
        if len(index_of) != n:
            raise ValueError("duplicate task ids")
        self.index_of = index_of
        res_index: Dict[str, int] = {}
        lay_index: Dict[str, int] = {}
        res_of = [0] * n
        lay_of = [0] * n
        indeg = [0] * n
        dependents: List[List[int]] = [[] for _ in range(n)]
        for i, t in enumerate(tasks):
            r = t.resource
            ri = res_index.get(r)
            if ri is None:
                ri = res_index[r] = len(res_index)
            res_of[i] = ri
            lay = t.layer
            li = lay_index.get(lay)
            if li is None:
                li = lay_index[lay] = len(lay_index)
            lay_of[i] = li
            indeg[i] = len(t.deps)
            for d in t.deps:
                j = index_of.get(d)
                if j is None:
                    raise ValueError(f"task {t.tid} depends on unknown {d}")
                dependents[j].append(i)
        self.dependents = [tuple(dd) for dd in dependents]
        self.indeg = indeg
        self.res_of = res_of
        self.res_names = list(res_index)
        self.layer_of = lay_of
        self.layer_names = list(lay_index)


def simulate_static(tasks: Sequence[Task],
                    resources: Optional[Dict[str, ResourceSpec]] = None,
                    durations=None,
                    cache: Optional[StaticCache] = None,
                    probe=None) -> SimResult:
    """Run a *static* task graph (no callbacks, no injection) over
    precomputed dependency arrays.

    Same causal semantics as :class:`DynamicSimulator` — multi-server FIFO
    stations, virtual-time processor-sharing channels, identical
    tie-breaking — but the hot loop indexes flat lists instead of dicts
    and defers ``TaskRecord`` materialization until a trace is read, so
    ``reannotate``-then-simulate sweep points skip all per-task object
    churn.  Parity with the seed engine and bit-exact parity with
    :class:`DynamicSimulator` are asserted by
    ``tests/test_engine_parity.py``.

    ``probe`` enables instrumentation with *zero* in-loop cost: the
    per-resource concurrency series and completion counters are derived
    post-hoc from the start/end arrays the loop fills anyway
    (:func:`_static_probe_series`), so the hot loop is byte-identical
    with and without a probe.
    """
    tasks = tasks if isinstance(tasks, list) else list(tasks)
    if cache is None:
        cache = StaticCache(tasks)
    n = cache.n
    resources = resources or {}
    if durations is None:
        durs = [t.duration for t in tasks]
    elif hasattr(durations, "tolist"):
        durs = durations.tolist()
        if len(durs) != n:
            raise ValueError("durations must align with tasks")
    else:
        if len(durations) != n:
            raise ValueError("durations must align with tasks")
        durs = [float(d) for d in durations]

    n_res = len(cache.res_names)
    shared = [False] * n_res
    servers = [1] * n_res
    for ri, name in enumerate(cache.res_names):
        spec = resources.get(name)
        if spec is not None:
            shared[ri] = spec.mode == "shared"
            servers[ri] = spec.servers

    res_of = cache.res_of
    tids = cache.tids            # equal-time ties break by tid, not index,
    dependents = cache.dependents    # mirroring DynamicSimulator
    indeg = list(cache.indeg)
    starts = [0.0] * n
    ends = [0.0] * n
    busy = [0.0] * n_res
    active = [0] * n_res
    queues: List[List[Tuple[float, int]]] = [[] for _ in range(n_res)]
    # Shared channels live as flat per-resource state (virtual-time GPS:
    # a task admitted with ``work`` at virtual time v finishes at the fixed
    # virtual time v + work; the clock runs at min(1, servers / n)):
    ch_heap: List[Optional[List[Tuple[float, int]]]] = [None] * n_res
    ch_vnow = [0.0] * n_res      # virtual clock
    ch_last = [0.0] * n_res      # real time of the last advance
    ch_n = [0] * n_res           # active tasks
    ch_epoch = [0] * n_res       # invalidates superseded completion events
    rel_eps = _REL_EPS
    events: List[Tuple[float, int, int, object]] = []
    # event tuple: (time, seq, code, payload); code 0 = fifo done
    # (payload = task index), code 1 = channel completion
    # (payload = (res index, epoch at issue))
    seq = 0
    now = 0.0
    n_done = 0
    push = heapq.heappush
    pop = heapq.heappop

    def reschedule(ri: int) -> None:
        nonlocal seq
        ch_epoch[ri] += 1
        m = ch_n[ri]
        if m:
            srv = servers[ri]
            rate = 1.0 if m <= srv else srv / m
            dv = ch_heap[ri][0][0] - ch_vnow[ri]
            t_next = now + (dv if dv > 0.0 else 0.0) / rate
            seq += 1
            push(events, (t_next, seq, 1, (ri, ch_epoch[ri])))

    def drain(ri: int) -> None:
        nonlocal seq
        q = queues[ri]
        cap = servers[ri]
        while q and active[ri] < cap:
            t_ready, _, i = pop(q)
            dur = durs[i]
            start = t_ready if t_ready > now else now
            end = start + dur
            active[ri] += 1
            busy[ri] += dur
            starts[i] = start
            ends[i] = end
            seq += 1
            push(events, (end, seq, 0, i))

    def enqueue(i: int, t_ready: float) -> None:
        ri = res_of[i]
        if shared[ri]:
            heap = ch_heap[ri]
            if heap is None:
                heap = ch_heap[ri] = []
            m = ch_n[ri]
            dt = t_ready - ch_last[ri]
            if dt > 0.0:                      # advance the virtual clock
                if m:
                    srv = servers[ri]
                    ch_vnow[ri] += dt * (1.0 if m <= srv else srv / m)
                ch_last[ri] = t_ready
            ch_n[ri] = m + 1
            push(heap, (ch_vnow[ri] + durs[i], tids[i], i))
            starts[i] = t_ready
            reschedule(ri)
        else:
            push(queues[ri], (t_ready, tids[i], i))
            drain(ri)

    for i in range(n):
        if indeg[i] == 0:
            enqueue(i, 0.0)

    while events:
        now, _, code, payload = pop(events)
        if code == 0:                       # fifo completion
            i = payload
            active[res_of[i]] -= 1
            n_done += 1
            for j in dependents[i]:
                indeg[j] -= 1
                if indeg[j] == 0:
                    enqueue(j, now)
            drain(res_of[i])
        else:                               # channel completion(s)
            ri, epoch = payload
            if epoch != ch_epoch[ri]:
                continue                    # superseded by a re-plan
            # advance the virtual clock to now
            m = ch_n[ri]
            dt = now - ch_last[ri]
            if dt > 0.0:
                if m:
                    srv = servers[ri]
                    ch_vnow[ri] += dt * (1.0 if m <= srv else srv / m)
                ch_last[ri] = now
            # the head is complete by construction (epoch was current);
            # pop it plus near-ties within the relative epsilon
            heap = ch_heap[ri]
            vf0, _, i = pop(heap)
            if vf0 > ch_vnow[ri]:           # absorb scheduling round-off
                ch_vnow[ri] = vf0
            m -= 1
            done = [i]
            while heap:
                vf, _, i2 = heap[0]
                if vf - vf0 > rel_eps * durs[i2]:
                    break
                pop(heap)
                m -= 1
                done.append(i2)
            ch_n[ri] = m
            if len(done) > 1:
                done.sort(key=tids.__getitem__)   # complete in tid order
            for i in done:
                busy[ri] += durs[i]
                ends[i] = now
                n_done += 1
                for j in dependents[i]:
                    indeg[j] -= 1
                    if indeg[j] == 0:
                        enqueue(j, now)
            reschedule(ri)

    if n_done != n:
        stuck = [i for i in range(n) if indeg[i] > 0]
        raise RuntimeError(
            f"deadlock/cycle: {len(stuck)} tasks never ran, e.g. "
            f"{[tasks[i].name for i in stuck[:5]]}")

    makespan = max(ends) if n else 0.0
    lay_of = cache.layer_of
    lay_lo = [float("inf")] * len(cache.layer_names)
    lay_hi = [float("-inf")] * len(cache.layer_names)
    for i in range(n):
        li = lay_of[i]
        s = starts[i]
        e = ends[i]
        if s < lay_lo[li]:
            lay_lo[li] = s
        if e > lay_hi[li]:
            lay_hi[li] = e
    layer_time = {name: (lay_lo[li], lay_hi[li])
                  for li, name in enumerate(cache.layer_names)}
    resource_busy = {name: busy[ri]
                     for ri, name in enumerate(cache.res_names)}

    if probe is not None:
        _static_probe_series(probe, cache, starts, ends)

    def materialize() -> List[TaskRecord]:
        return [TaskRecord(tasks[i], starts[i], ends[i]) for i in range(n)]

    return SimResult(makespan=makespan, records_thunk=materialize,
                     resource_busy=resource_busy, layer_time=layer_time)


def _static_probe_series(probe, cache: StaticCache, starts: Sequence[float],
                         ends: Sequence[float]) -> None:
    """Derive ``simulate_static`` instrumentation after the run: a
    per-resource active-task concurrency gauge (+1 at each start, -1 at
    each end, starts-before-ends on ties so the level never dips
    negative) and a global completion counter over the end times."""
    n = cache.n
    if not n:
        return
    res_of = cache.res_of
    for ri, name in enumerate(cache.res_names):
        deltas = []
        for i in range(n):
            if res_of[i] == ri:
                deltas.append((starts[i], 1))
                deltas.append((ends[i], -1))
        g = probe.gauge(f"static/{name}/active", unit="tasks")
        level = 0
        for t, d in sorted(deltas, key=lambda td: (td[0], -td[1])):
            level += d
            g.set(t, level)
    c = probe.counter("static/tasks_completed")
    for t in sorted(ends[:n]):
        c.add(t)


# ---------------------------------------------------------------------------
# Array-backed fast path for dynamic (injected) task graphs
# ---------------------------------------------------------------------------


class GraphTemplate:
    """Precompiled structure of a small task graph injected repeatedly.

    The serving simulator's task-graph mode injects the same phase shape
    (chunked prefill/decode compute with KV-write DMAs) once per scheduler
    decision — thousands of times per run.  Building ``Task`` objects and
    re-walking their dependencies on every injection is exactly the
    per-task churn the dynamic fast path removes: a template captures the
    local dependency CSR, resource/layer names, and record metadata once,
    so :meth:`DynamicSimulator.inject_template` instantiates it with a
    handful of list extends and no object construction.

    ``tasks`` must use dense local ids ``0..n-1`` with local-only deps;
    ``tail`` names the task whose completion fires the per-instance
    ``on_done`` callback (default: the last task).
    """

    __slots__ = ("n", "names", "kinds", "res_names", "layer_names",
                 "res_of", "layer_of", "deps", "dependents", "indeg",
                 "roots", "tail", "nbytes", "flops")

    def __init__(self, tasks: Sequence[Task], tail: Optional[int] = None):
        n = len(tasks)
        self.n = n
        if [t.tid for t in tasks] != list(range(n)):
            raise ValueError("template tasks must use dense local ids 0..n-1")
        self.deps = [tuple(t.deps) for t in tasks]
        self.names = [t.name for t in tasks]
        self.kinds = [t.kind for t in tasks]
        self.nbytes = [t.nbytes for t in tasks]
        self.flops = [t.flops for t in tasks]
        res_index: Dict[str, int] = {}
        lay_index: Dict[str, int] = {}
        self.res_of = [res_index.setdefault(t.resource, len(res_index))
                       for t in tasks]
        self.layer_of = [lay_index.setdefault(t.layer, len(lay_index))
                         for t in tasks]
        self.res_names = list(res_index)
        self.layer_names = list(lay_index)
        dependents: List[List[int]] = [[] for _ in range(n)]
        self.indeg = [0] * n
        for i, t in enumerate(tasks):
            self.indeg[i] = len(t.deps)
            for d in t.deps:
                if not 0 <= d < n:
                    raise ValueError(f"template task {i}: non-local dep {d}")
                dependents[d].append(i)
        self.dependents = [tuple(dd) for dd in dependents]
        self.roots = [i for i in range(n) if self.indeg[i] == 0]
        self.tail = n - 1 if tail is None else tail
        if not 0 <= self.tail < n:
            raise ValueError(f"tail {self.tail} out of range")


class DynamicCache:
    """Growable flat task structure for the dynamic fast path.

    The static fast path's :class:`StaticCache` precomputes a dependency
    CSR for a *fixed* task list; dynamic injection breaks that premise.
    A DynamicCache keeps the same flat layout — parallel lists indexed by
    a dense task index — but assigns each task its index *on arrival*
    (initial list order, then injection order).  Indices are stable: they
    never move as the arrays grow, so the event loop keeps integer-
    indexing flat lists while ``tid -> index`` remapping stays O(1) per
    lookup and is skipped entirely for template instances (their indices
    are a contiguous block known at injection).

    ``from_static`` seeds the dynamic structure from a precomputed
    :class:`StaticCache` (``CompiledGraph.sim_cache()``), so traffic
    injected on top of a compiled graph reuses its CSR instead of
    re-walking every dependency.
    """

    __slots__ = ("tids", "index_of", "tasks", "durs", "res_of", "layer_of",
                 "indeg", "dependents", "dep_base", "res_names", "res_index",
                 "layer_names", "layer_index", "instances")

    def __init__(self):
        self.tids: List[int] = []
        self.index_of: Dict[int, int] = {}
        self.tasks: List[Optional[Task]] = []   # None for template instances
        self.durs: List[float] = []
        self.res_of: List[int] = []
        self.layer_of: List[int] = []
        self.indeg: List[int] = []
        # ``dependents[i]`` holds ids relative to ``dep_base[i]`` — 0 for
        # individually added tasks (absolute ids), the instance base for
        # template tasks, whose dependents alias the template's local
        # tuples (no per-instance list is ever built).
        self.dependents: List[Sequence[int]] = []
        self.dep_base: List[int] = []
        self.res_names: List[str] = []
        self.res_index: Dict[str, int] = {}
        self.layer_names: List[str] = []
        self.layer_index: Dict[str, int] = {}
        # (base index, template) per instantiation, base ascending — the
        # record materializer recovers names/kinds from here.
        self.instances: List[Tuple[int, GraphTemplate]] = []

    @property
    def n(self) -> int:
        return len(self.tids)

    @classmethod
    def from_static(cls, cache: StaticCache, tasks: Sequence[Task],
                    durations=None) -> "DynamicCache":
        """Seed from a :class:`StaticCache` — the CSR of the static prefix
        is copied, not recomputed from ``Task.deps``."""
        c = cls()
        c.tids = list(cache.tids)
        c.index_of = dict(cache.index_of)
        c.tasks = list(tasks)
        if durations is None:
            c.durs = [t.duration for t in tasks]
        else:
            c.durs = [float(d) for d in durations]
            if len(c.durs) != cache.n:
                raise ValueError("durations must align with tasks")
        c.res_of = list(cache.res_of)
        c.layer_of = list(cache.layer_of)
        c.indeg = list(cache.indeg)
        c.dependents = [list(dd) for dd in cache.dependents]
        c.dep_base = [0] * cache.n
        c.res_names = list(cache.res_names)
        c.res_index = {name: ri for ri, name in enumerate(cache.res_names)}
        c.layer_names = list(cache.layer_names)
        c.layer_index = {name: li
                         for li, name in enumerate(cache.layer_names)}
        return c

    def intern_resource(self, name: str) -> int:
        ri = self.res_index.get(name)
        if ri is None:
            ri = self.res_index[name] = len(self.res_names)
            self.res_names.append(name)
        return ri

    def intern_layer(self, name: str) -> int:
        li = self.layer_index.get(name)
        if li is None:
            li = self.layer_index[name] = len(self.layer_names)
            self.layer_names.append(name)
        return li

    def add_task(self, task: Task, dur: float) -> int:
        """Append one task (dependencies are wired by the simulator, which
        knows which are already complete)."""
        if task.tid in self.index_of:
            raise ValueError(f"duplicate task id {task.tid}")
        i = len(self.tids)
        self.index_of[task.tid] = i
        self.tids.append(task.tid)
        self.tasks.append(task)
        self.durs.append(dur)
        self.res_of.append(self.intern_resource(task.resource))
        self.layer_of.append(self.intern_layer(task.layer))
        self.indeg.append(0)
        self.dependents.append([])
        self.dep_base.append(0)
        return i

    def task_of(self, i: int) -> Task:
        """The ``Task`` at index ``i``, materializing template instances
        lazily (binary search over the instance bases)."""
        t = self.tasks[i]
        if t is not None:
            return t
        from bisect import bisect_right
        k = bisect_right(self.instances, i, key=lambda inst: inst[0]) - 1
        base, tpl = self.instances[k]
        j = i - base
        t = Task(tid=self.tids[i], name=tpl.names[j],
                 layer=self.layer_names[self.layer_of[i]],
                 resource=self.res_names[self.res_of[i]],
                 duration=self.durs[i], kind=tpl.kinds[j],
                 nbytes=tpl.nbytes[j], flops=tpl.flops[j])
        self.tasks[i] = t
        return t


class DynamicSimulator:
    """Event-driven engine for *dynamic* simulations over FIFO and
    bandwidth-shared resources.

    The event loop is instance-level state, so timed callbacks
    (:meth:`at`) and completion observers (``on_complete``) can inject
    new tasks (:meth:`inject`) while the simulation is running — dynamic
    arrivals preempting a static task graph.  The hot loop indexes the
    flat :class:`DynamicCache` arrays, resource specs are resolved once
    per resource name, and ``TaskRecord``/name construction is deferred
    until a trace is read.  :meth:`inject_template` amortizes the
    structure of a repeatedly injected subgraph (one CSR walk per
    :class:`GraphTemplate`, list extends per instance).  Parity with the
    frozen seed engine, static graphs and mid-flight injection alike, is
    asserted task-for-task in ``tests/test_engine_parity.py``.
    """

    def __init__(self, tasks: Iterable[Task] = (),
                 resources: Optional[Dict[str, ResourceSpec]] = None,
                 durations=None,
                 on_complete: Optional[Callable[[Task, float], None]] = None,
                 cache: Optional[StaticCache] = None,
                 probe=None):
        """``durations`` optionally overrides annotated durations (aligned
        with ``tasks``); ``cache`` optionally seeds the dependency layout
        from a precomputed :class:`StaticCache` of the same task list.
        ``probe`` (a :class:`repro.obs.probe.Probe`) enables event-loop
        instrumentation: per-kind event counters plus active/share gauges
        on bandwidth-shared channels.  Probes only read simulation state —
        results are bit-identical with or without one."""
        tasks = tasks if isinstance(tasks, list) else list(tasks)
        self.resources = dict(resources or {})
        self.on_complete = on_complete
        self.probe = probe
        self._chan_gauges: Dict[int, Tuple] = {}
        if durations is not None and len(durations) != len(tasks):
            raise ValueError("durations must align with tasks")
        if cache is not None:
            if cache.n != len(tasks):
                raise ValueError("cache does not match tasks")
            self.cache = DynamicCache.from_static(cache, tasks, durations)
        else:
            self.cache = c = DynamicCache()
            for k, t in enumerate(tasks):
                i = c.add_task(
                    t, t.duration if durations is None
                    else float(durations[k]))
                c.indeg[i] = len(t.deps)
            for t in tasks:
                for d in t.deps:
                    j = c.index_of.get(d)
                    if j is None:
                        raise ValueError(
                            f"task {t.tid} depends on unknown {d}")
                    c.dependents[j].append(c.index_of[t.tid])
        self._next_tid = max(self.cache.tids, default=-1) + 1
        # ---- runtime state, parallel to cache indices ----
        n = self.cache.n
        self._starts = [0.0] * n
        self._ends = [0.0] * n
        self._done = [False] * n
        self._n_done = 0
        self._on_done: Dict[int, Callable[[float], None]] = {}
        # ---- per-resource runtime state, parallel to cache.res_names;
        # grown lazily as resources intern (spec resolved once per name)
        self._shared: List[bool] = []
        self._servers: List[int] = []
        self._active: List[int] = []
        self._busy: List[float] = []
        self._used: List[bool] = []   # ever scheduled a task (reported in
        #                               resource_busy, even when all-zero)
        self._queues: List[List[Tuple[float, int, int]]] = []
        self._ch_heap: List[Optional[List[Tuple[float, int, int]]]] = []
        self._ch_vnow: List[float] = []
        self._ch_last: List[float] = []
        self._ch_n: List[int] = []
        self._ch_epoch: List[int] = []
        # per-template interned instantiation payloads (mapped resource and
        # layer ids + reusable extend tuples), keyed by id(template)
        self._tpl_ids: Dict[int, Tuple] = {}
        self._lanes: List = []  # ServiceLane | TemplateLane
        self._now = 0.0
        self._seq = 0
        self._running = False
        self._events: List[Tuple[float, int, str, object]] = []
        self._grow_resources()

    # ------------------------------------------------------------------
    # Dynamic injection API
    # ------------------------------------------------------------------

    @property
    def now(self) -> float:
        """Current simulation time."""
        return self._now

    def at(self, t: float, fn: Callable[[], None]) -> None:
        """Schedule ``fn`` to run inside the event loop at time ``t``.

        Callbacks at equal times run in scheduling order.  ``fn`` may call
        :meth:`inject` / :meth:`at` — this is how open-loop arrivals and
        scheduler timeouts enter a running simulation.
        """
        if t < self._now - 1e-18:
            raise ValueError(f"cannot schedule at {t} < now ({self._now})")
        self._seq += 1
        heapq.heappush(self._events,
                       (max(t, self._now), self._seq, "call", fn))

    def every(self, interval: float, fn: Callable[[], bool],
              start: Optional[float] = None) -> None:
        """Run ``fn`` periodically inside the event loop (health checks,
        autoscaler ticks).  The first tick fires at ``start`` (default
        ``now + interval``), then every ``interval`` for as long as
        ``fn()`` returns truthy.  Exactly one pending tick lives on the
        event heap at a time, so a falsy return ends the chain, the heap
        can drain and :meth:`run` can terminate: ``fn`` must return False
        once the condition it monitors is resolved."""
        if not (interval > 0.0) or not math.isfinite(interval):
            raise ValueError(f"every(): interval must be finite and > 0, "
                             f"got {interval!r}")

        def _tick() -> None:
            if fn():
                self.at(self._now + interval, _tick)

        self.at(self._now + interval if start is None else start, _tick)

    def next_task_id(self) -> int:
        """A fresh task id (monotone counter above every existing id)."""
        return self._next_tid

    def lane(self, resource: str,
             name_fn: Optional[Callable[[str, object], str]] = None
             ) -> ServiceLane:
        """Open a :class:`ServiceLane` on a dedicated single-server
        resource (see the class docstring for the contract)."""
        ln = ServiceLane(self, resource, name_fn)
        self._lanes.append(ln)
        return ln

    def template_lane(self, resource: str,
                      step_durs: Optional[Callable] = None) -> TemplateLane:
        """Open a :class:`TemplateLane` — graph-structured phases with
        one event per phase (see the class docstring for the contract)."""
        ln = TemplateLane(self, resource, step_durs)
        self._lanes.append(ln)
        return ln

    def inject(self, task: Task,
               on_done: Optional[Callable[[float], None]] = None) -> Task:
        """Add ``task`` to a (possibly running) simulation.

        Dependencies may reference completed or in-flight tasks.  The task
        becomes ready once its outstanding dependencies finish (immediately
        if there are none).  ``on_done(now)`` fires when this task
        completes (after dependents are released and the global
        ``on_complete``)."""
        c = self.cache
        for d in task.deps:
            if d not in c.index_of:
                raise ValueError(f"task {task.tid} depends on unknown {d}")
        i = c.add_task(task, task.duration)
        if task.tid >= self._next_tid:
            self._next_tid = task.tid + 1
        self._starts.append(0.0)
        self._ends.append(0.0)
        self._done.append(False)
        if on_done is not None:
            self._on_done[i] = on_done
        if not self._running:
            c.indeg[i] = len(task.deps)
            for d in task.deps:
                c.dependents[c.index_of[d]].append(i)
            return task
        outstanding = 0
        for d in task.deps:
            j = c.index_of[d]
            if not self._done[j]:
                outstanding += 1
                c.dependents[j].append(i)
        c.indeg[i] = outstanding
        if not outstanding:
            self._enqueue(i, self._now)
        return task

    def inject_template(self, tpl: GraphTemplate, durations: Sequence[float],
                        on_done: Optional[Callable[[float], None]] = None
                        ) -> int:
        """Instantiate ``tpl`` with per-instance ``durations``; all
        template roots become ready now.  Returns the instance's base task
        id (ids are ``base .. base + tpl.n - 1`` in template order).

        Template instances are pure array extends: no Task objects, no
        tid remapping (the block's indices are contiguous), no dependency
        walk.  Their ids are therefore *not* valid dependency targets for
        later :meth:`inject` calls, and the global ``on_complete``
        observer — which receives ``Task`` objects — materializes them
        lazily; ``on_done`` fires when the template's tail completes.
        """
        if len(durations) != tpl.n:
            raise ValueError("durations must align with the template")
        c = self.cache
        base = c.n
        tid0 = self._next_tid
        self._next_tid = tid0 + tpl.n
        ids = self._tpl_ids.get(id(tpl))
        if ids is None:
            # intern once per (simulator, template): resource/layer ids
            # mapped into this simulator's index space, plus reusable
            # extend payloads (tuples extend at C speed)
            res_ids = tuple(c.intern_resource(r) for r in tpl.res_names)
            lay_ids = tuple(c.intern_layer(name) for name in tpl.layer_names)
            ids = self._tpl_ids[id(tpl)] = (
                tuple(res_ids[r] for r in tpl.res_of),
                tuple(lay_ids[li] for li in tpl.layer_of),
                tuple(tpl.indeg), (None,) * tpl.n, (0.0,) * tpl.n,
                (False,) * tpl.n)
            self._grow_resources()
        mapped_res, mapped_lay, indeg, nones, zeros, falses = ids
        c.tids.extend(range(tid0, tid0 + tpl.n))
        c.tasks.extend(nones)
        c.durs.extend(durations)
        c.res_of.extend(mapped_res)
        c.layer_of.extend(mapped_lay)
        c.indeg.extend(indeg)
        c.dependents.extend(tpl.dependents)   # shared local-id tuples
        c.dep_base.extend([base] * tpl.n)
        c.instances.append((base, tpl))
        self._starts.extend(zeros)
        self._ends.extend(zeros)
        self._done.extend(falses)
        if on_done is not None:
            self._on_done[base + tpl.tail] = on_done
        if self._running:
            for j in tpl.roots:
                self._enqueue(base + j, self._now)
        return tid0

    # ------------------------------------------------------------------
    # Event loop
    # ------------------------------------------------------------------

    def _grow_resources(self) -> None:
        """Extend per-resource runtime arrays to cover newly interned
        resources, resolving each spec exactly once."""
        names = self.cache.res_names
        for ri in range(len(self._servers), len(names)):
            spec = self.resources.get(names[ri])
            self._shared.append(spec is not None and spec.mode == "shared")
            self._servers.append(spec.servers if spec is not None else 1)
            self._active.append(0)
            self._used.append(False)
            self._busy.append(0.0)
            self._queues.append([])
            self._ch_heap.append(None)
            self._ch_vnow.append(0.0)
            self._ch_last.append(0.0)
            self._ch_n.append(0)
            self._ch_epoch.append(0)

    def _reschedule_channel(self, ri: int) -> None:
        self._ch_epoch[ri] += 1
        m = self._ch_n[ri]
        if m:
            srv = self._servers[ri]
            rate = 1.0 if m <= srv else srv / m
            dv = self._ch_heap[ri][0][0] - self._ch_vnow[ri]
            self._seq += 1
            heapq.heappush(
                self._events,
                (self._now + (dv if dv > 0.0 else 0.0) / rate, self._seq,
                 "chan", (ri, self._ch_epoch[ri])))

    def _chan_probe(self, ri: int, t: float) -> None:
        """Shared-channel active/share gauges at a rate-change boundary —
        called only when a probe is installed."""
        g = self._chan_gauges.get(ri)
        if g is None:
            name = self.cache.res_names[ri]
            g = self._chan_gauges[ri] = (
                self.probe.gauge(f"engine/chan/{name}/active", unit="tasks"),
                self.probe.gauge(f"engine/chan/{name}/share", unit="frac"))
        m = self._ch_n[ri]
        srv = self._servers[ri]
        g[0].set(t, m)
        g[1].set(t, 1.0 if not m or m <= srv else srv / m)

    def _drain(self, ri: int) -> None:
        q = self._queues[ri]
        cap = self._servers[ri]
        active = self._active
        durs = self.cache.durs
        now = self._now
        while q and active[ri] < cap:
            t_ready, _, i = heapq.heappop(q)
            dur = durs[i]
            start = t_ready if t_ready > now else now
            active[ri] += 1
            self._busy[ri] += dur
            self._starts[i] = start
            self._ends[i] = start + dur
            self._seq += 1
            heapq.heappush(self._events, (start + dur, self._seq, "done", i))

    def _enqueue(self, i: int, t_ready: float) -> None:
        c = self.cache
        ri = c.res_of[i]
        if ri >= len(self._servers):
            self._grow_resources()
        self._used[ri] = True
        if not self._shared[ri]:
            # FIFO: immediate dispatch when a server is free and nothing
            # queues ahead — same outcome as push-then-drain, without the
            # heap round-trip (the overwhelmingly common case for the
            # serving simulator's one-phase-at-a-time replica resources).
            if not self._queues[ri] and self._active[ri] < self._servers[ri]:
                dur = c.durs[i]
                now = self._now
                start = t_ready if t_ready > now else now
                self._active[ri] += 1
                self._busy[ri] += dur
                self._starts[i] = start
                self._ends[i] = start + dur
                self._seq += 1
                heapq.heappush(self._events,
                               (start + dur, self._seq, "done", i))
            else:
                heapq.heappush(self._queues[ri], (t_ready, c.tids[i], i))
                self._drain(ri)
            return
        heap = self._ch_heap[ri]
        if heap is None:
            heap = self._ch_heap[ri] = []
        m = self._ch_n[ri]
        dt = t_ready - self._ch_last[ri]
        if dt > 0.0:                          # advance the virtual clock
            if m:
                srv = self._servers[ri]
                self._ch_vnow[ri] += dt * (1.0 if m <= srv else srv / m)
            self._ch_last[ri] = t_ready
        self._ch_n[ri] = m + 1
        heapq.heappush(heap, (self._ch_vnow[ri] + c.durs[i],
                              c.tids[i], i))
        self._starts[i] = t_ready
        self._reschedule_channel(ri)
        if self.probe is not None:
            self._chan_probe(ri, t_ready)

    def run(self) -> SimResult:
        if self._running or self._n_done:
            raise RuntimeError(
                "DynamicSimulator.run() may only be called once")
        self._running = True
        c = self.cache
        indeg = c.indeg
        for i in range(c.n):
            if not indeg[i]:
                self._enqueue(i, 0.0)

        # The hot loop binds every per-task array to a local: the lists
        # are grown strictly in place (append/extend), so the bindings
        # stay valid across injections from callbacks.  The completion
        # path (_complete) is inlined — it runs once per task.
        events = self._events
        res_of = c.res_of
        durs = c.durs
        tids = c.tids
        indeg = c.indeg
        dependents = c.dependents
        dep_base = c.dep_base
        done_flags = self._done
        active = self._active
        queues = self._queues
        busy = self._busy
        starts = self._starts
        ends = self._ends
        used = self._used
        shared_res = self._shared
        servers = self._servers
        on_done = self._on_done
        enqueue = self._enqueue
        rel_eps = _REL_EPS
        pop = heapq.heappop
        push = heapq.heappush
        n_res_known = len(servers)
        n_done = 0
        # Observability: one local None-check per event when disabled.
        prb = self.probe
        if prb is not None:
            p_done = prb.counter("engine/fifo_completions")
            p_lane = prb.counter("engine/lane_completions")
            p_call = prb.counter("engine/callbacks")
            p_chan = prb.counter("engine/chan_completions")
        while events:
            now, _, kind, payload = pop(events)
            self._now = now
            if kind == "done":                # fifo completion
                i = payload
                ri = res_of[i]
                active[ri] -= 1
                done_flags[i] = True
                n_done += 1
                off = dep_base[i]
                for j in dependents[i]:
                    j += off
                    indeg[j] -= 1
                    if not indeg[j]:
                        # inlined FIFO immediate dispatch (the dominant
                        # release path); everything else falls back to the
                        # general _enqueue
                        rj = res_of[j]
                        if (rj < n_res_known and not shared_res[rj]
                                and not queues[rj]
                                and active[rj] < servers[rj]):
                            dur = durs[j]
                            used[rj] = True
                            starts[j] = now
                            end = now + dur
                            ends[j] = end
                            active[rj] += 1
                            busy[rj] += dur
                            self._seq += 1
                            push(events, (end, self._seq, "done", j))
                        else:
                            enqueue(j, now)
                            n_res_known = len(servers)
                cb = self.on_complete
                if cb is not None:
                    cb(c.task_of(i), now)
                if on_done:
                    h = on_done.pop(i, None)
                    if h is not None:
                        h(now)
                    n_res_known = len(servers)
                if queues[ri]:
                    self._drain(ri)
                if prb is not None:
                    p_done.add(now)
            elif kind == "lane":
                ln, handler, epoch = payload
                if epoch != ln.epoch:
                    continue                  # superseded by a truncation
                ln.busy = False
                handler(self._now)
                if prb is not None:
                    p_lane.add(self._now)
            elif kind == "call":
                payload()
                if prb is not None:
                    p_call.add(self._now)
            else:                             # channel completion(s)
                ri, epoch = payload
                if epoch != self._ch_epoch[ri]:
                    continue                  # superseded by a re-plan
                now = self._now
                m = self._ch_n[ri]
                dt = now - self._ch_last[ri]
                if dt > 0.0:
                    if m:
                        srv = self._servers[ri]
                        self._ch_vnow[ri] += dt * (1.0 if m <= srv
                                                   else srv / m)
                    self._ch_last[ri] = now
                heap = self._ch_heap[ri]
                vf0, _, i = pop(heap)
                if vf0 > self._ch_vnow[ri]:   # absorb scheduling round-off
                    self._ch_vnow[ri] = vf0
                m -= 1
                done = [i]
                while heap:
                    vf, _, i2 = heap[0]
                    if vf - vf0 > rel_eps * durs[i2]:
                        break
                    pop(heap)
                    m -= 1
                    done.append(i2)
                self._ch_n[ri] = m
                if len(done) > 1:
                    done.sort(key=tids.__getitem__)  # complete in tid order
                for i in done:
                    busy[ri] += durs[i]
                    ends[i] = now
                    done_flags[i] = True
                    n_done += 1
                    off = dep_base[i]
                    for j in dependents[i]:
                        j += off
                        indeg[j] -= 1
                        if not indeg[j]:
                            enqueue(j, now)
                    cb = self.on_complete
                    if cb is not None:
                        cb(c.task_of(i), now)
                    if on_done:
                        h = on_done.pop(i, None)
                        if h is not None:
                            h(now)
                    if prb is not None:
                        p_chan.add(now)
                self._reschedule_channel(ri)
                if prb is not None:
                    self._chan_probe(ri, now)

        self._n_done = n_done
        if self._n_done != c.n:
            stuck = [i for i in range(c.n) if c.indeg[i] > 0]
            raise RuntimeError(
                f"deadlock/cycle: {len(stuck)} tasks never ran, e.g. "
                f"{[c.task_of(i).name for i in stuck[:5]]}")
        self._running = False

        n = c.n
        starts, ends = self._starts, self._ends
        makespan = max(ends) if n else 0.0
        lay_of = c.layer_of
        lay_lo = [float("inf")] * len(c.layer_names)
        lay_hi = [float("-inf")] * len(c.layer_names)
        for i in range(n):
            li = lay_of[i]
            if starts[i] < lay_lo[li]:
                lay_lo[li] = starts[i]
            if ends[i] > lay_hi[li]:
                lay_hi[li] = ends[i]
        layer_time = {name: (lay_lo[li], lay_hi[li])
                      for li, name in enumerate(c.layer_names)
                      if lay_lo[li] != float("inf")}
        resource_busy = {name: self._busy[ri]
                         for ri, name in enumerate(c.res_names)
                         if self._used[ri]}

        lanes = [ln for ln in self._lanes if ln._nonempty()]
        for ln in lanes:
            makespan = max(makespan, ln._merge(resource_busy, layer_time))

        tid_base = self._next_tid

        def materialize() -> List[TaskRecord]:
            out = [TaskRecord(c.task_of(i), starts[i], ends[i])
                   for i in range(n)]
            base = tid_base
            for ln in lanes:
                recs = ln._materialize(base)
                out.extend(recs)
                base += len(recs)
            return out

        return SimResult(makespan=makespan, records_thunk=materialize,
                         resource_busy=resource_busy, layer_time=layer_time)
