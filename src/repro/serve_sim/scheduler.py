"""Pluggable batching policies for the virtual serving simulator.

Each policy answers one question — *what should this replica do next?* —
given its slot occupancy and the shared request queue.  The simulator
(``repro.serve_sim.simulator``) invokes :meth:`BatchScheduler.decide`
whenever a replica goes idle (after a prefill/decode task completes, on a
request arrival, or at a requested wake-up time) and turns the returned
action into a task on the replica's DES resource.

Policies (virtual counterparts of real serving loops):

  * :class:`ContinuousBatchingScheduler` — slot-based continuous batching,
    mirroring the *measured* ``repro.launch.serve.BatchedServer`` loop
    admit-for-admit and step-for-step (asserted by
    ``tests/test_serve_sim.py``): admit queued requests one at a time into
    free slots, then run one decode step for every active slot; a finished
    request's slot is refilled from the queue before the next step.
  * :class:`BucketedPrefillScheduler` — dynamic batching with bucketed
    prefill: all admissible queued requests are prefilled together, each
    prompt padded to the next bucket boundary (padding is paid as extra
    prefill tokens); decode then continues slot-style.
  * :class:`StaticBatchScheduler` — classic static batching: wait until
    ``batch_size`` requests are queued (or ``max_wait`` expired), run the
    whole batch to completion before admitting again.  Finished requests
    hold their slot until the batch drains — the padding waste that
    continuous batching eliminates, now measurable in the virtual model.
"""
from __future__ import annotations

import abc
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional, Sequence, Union

from repro.serve_sim.workload import Request


@dataclass(slots=True)
class InFlight:
    """One admitted request's runtime state on a replica."""

    req: Request
    slot: int
    ctx: int = 0                 # cached tokens (prompt + generated)
    generated: int = 0
    t_admit: float = 0.0
    t_first: Optional[float] = None   # end of the step emitting token 1
    done: bool = False           # finished but still holding its slot

    @property
    def finished(self) -> bool:
        return self.generated >= self.req.output_tokens


@dataclass
class ReplicaState:
    """Slot occupancy of one replica (owned by the simulator)."""

    index: int
    slots: int
    active: List[InFlight] = field(default_factory=list)
    busy: bool = False

    @property
    def free_slots(self) -> int:
        return self.slots - len(self.active)

    @property
    def decoding(self) -> List[InFlight]:
        """Slots that still generate tokens (excludes held finished slots)."""
        return [f for f in self.active if not f.done]

    @property
    def any_decoding(self) -> bool:
        """True if any slot still generates tokens — the O(n) early-exit
        check the per-step ``decide`` hot path needs (``decoding`` builds
        a list)."""
        return any(not f.done for f in self.active)


# ---- actions -------------------------------------------------------------


@dataclass(frozen=True)
class Prefill:
    """Admit ``reqs`` (already popped from the queue) and charge
    ``tokens`` prefill tokens (includes any bucket padding)."""

    reqs: Sequence[Request]
    tokens: int


@dataclass(frozen=True)
class Decode:
    """Run one decode step for every decoding slot."""


@dataclass(frozen=True)
class Wait:
    """Re-invoke ``decide`` at time ``t`` (batching timeout)."""

    t: float


@dataclass(frozen=True)
class Shed:
    """Drop ``reqs`` (already popped from the queue) — graceful-degradation
    load shedding.  The simulator accounts them as shed and immediately
    re-invokes ``decide`` for the replica's real next action."""

    reqs: Sequence[Request]


Action = Union[Prefill, Decode, Wait, Shed, None]

#: ``Decode`` carries no state — reuse one instance in the per-step hot path.
_DECODE = Decode()


def _bucket(n: int, bucket: int) -> int:
    """Round ``n`` up to the next multiple of ``bucket``."""
    return -(-n // bucket) * bucket if bucket > 1 else n


class BatchScheduler(abc.ABC):
    """One batching policy.  Stateless w.r.t. time: all runtime state lives
    in :class:`ReplicaState` and the shared queue, so a fresh instance per
    simulation run is cheap and the policy is trivially seedable."""

    name: str = "abstract"
    #: finished requests keep their slot until every batch member finishes
    hold_finished: bool = False
    #: policy guarantees that once a decode step is issued and no admission
    #: is possible (no free slot, or ``hold_finished`` blocking admissions),
    #: every subsequent ``decide`` returns ``Decode`` until a slot finishes.
    #: The simulator then fuses the steps up to the next finish into one
    #: task (exact per-step costs, ~10x fewer events).  Custom policies
    #: whose decisions depend on time or queue state mid-batch must leave
    #: this False.
    steady_decode: bool = False
    #: weaker contract enabling the *speculative* decode leap: between slot
    #: finishes, a ``Decode`` decision is a pure function of the queue and
    #: the slot occupancy — it may change when the queue changes (an
    #: arrival) but only if admission is possible (a free slot exists and
    #: no ``hold_finished`` batch is draining); with admission blocked the
    #: decision must repeat.  The simulator then fuses decode steps
    #: optimistically even while admission is possible, snapshots the
    #: per-step boundaries, and rolls the fused task back to the first
    #: boundary at/after an arrival that lands mid-leap, replaying from
    #: there per the policy's real decisions — exact parity with per-step
    #: simulation (tests/test_serve_sim.py).  The same contract powers
    #: both serving representations: the express ``ServiceLane`` truncates
    #: its fused task, and task-graph mode (``phase_tasks=N``) books the
    #: leap as one ``TemplateLane`` burst of per-step
    #: template instances and truncates the burst at a snapshot boundary.
    #: Policies whose mid-batch decisions depend on ``now``, on step
    #: count, or on queue depth while no slot is free must leave this
    #: False.
    decode_stable: bool = False

    @abc.abstractmethod
    def decide(self, replica: ReplicaState, queue: Deque[Request],
               now: float) -> Action:
        """Pick the replica's next action.  May ``popleft`` requests off
        ``queue`` (they are then owned by the returned :class:`Prefill`)."""


class ContinuousBatchingScheduler(BatchScheduler):
    """Slot-based continuous batching — the virtual twin of the measured
    ``repro.launch.serve.BatchedServer`` loop: admit one queued request per
    free slot (sequential prefill), decode every active slot, refill freed
    slots before the next step."""

    name = "continuous"
    steady_decode = True
    decode_stable = True

    def decide(self, replica: ReplicaState, queue: Deque[Request],
               now: float) -> Action:
        if queue and len(replica.active) < replica.slots:
            req = queue.popleft()
            return Prefill((req,), req.prompt_tokens)
        return _DECODE if replica.any_decoding else None


class BucketedPrefillScheduler(BatchScheduler):
    """Dynamic batching with bucketed prefill: admit every admissible
    queued request at once, padding each prompt to the next ``bucket``
    boundary (the padding cost is real prefill work)."""

    name = "bucketed"
    steady_decode = True
    decode_stable = True

    def __init__(self, bucket: int = 128):
        if bucket < 1:
            raise ValueError("bucket must be >= 1")
        self.bucket = bucket

    def decide(self, replica: ReplicaState, queue: Deque[Request],
               now: float) -> Action:
        if queue and replica.free_slots > 0:
            n = min(len(queue), replica.free_slots)
            reqs = [queue.popleft() for _ in range(n)]
            tokens = sum(_bucket(r.prompt_tokens, self.bucket) for r in reqs)
            return Prefill(tuple(reqs), tokens)
        return _DECODE if replica.any_decoding else None


class StaticBatchScheduler(BatchScheduler):
    """Classic static batching: form a batch of ``batch_size`` (or whatever
    arrived within ``max_wait`` of the oldest queued request), run it to
    completion, repeat.  Prompts are padded to the longest in the batch."""

    name = "static"
    hold_finished = True
    steady_decode = True
    decode_stable = True

    def __init__(self, batch_size: int = 8, max_wait: float = 0.5):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        self.batch_size = batch_size
        self.max_wait = max_wait

    def decide(self, replica: ReplicaState, queue: Deque[Request],
               now: float) -> Action:
        if replica.active:
            if replica.any_decoding:
                return _DECODE
            return None       # simulator releases the drained batch
        if not queue:
            return None
        deadline = queue[0].t_arrive + self.max_wait
        if len(queue) < self.batch_size and now < deadline:
            return Wait(deadline)
        n = min(len(queue), self.batch_size, replica.slots)
        reqs = [queue.popleft() for _ in range(n)]
        longest = max(r.prompt_tokens for r in reqs)
        return Prefill(tuple(reqs), longest * n)
        # padding to the longest prompt: the whole batch pays max-length
        # prefill, the static-batching cost continuous batching removes


class LoadSheddingScheduler(ContinuousBatchingScheduler):
    """Continuous batching with graceful-degradation admission control.

    When the shared queue grows past ``max_queue`` — the queue-depth
    proxy for a blown ETA, e.g. during a replica outage — the scheduler
    sheds queued requests down to ``shed_to`` before admitting.  The drop
    set is priority-aware: lowest :attr:`Request.priority` first, and
    newest-first among equals (older requests have waited longest and are
    closest to service, so fresh low-priority load is the cheapest to
    refuse).  Shedding is deterministic — no RNG — so fault scenarios
    reproduce bit-identically.

    Decode decisions are inherited unchanged, but a ``decide`` call can
    now return :class:`Shed` whenever the queue is deep — even mid-batch
    with admission blocked — so *both* decode-leap contracts are off:
    fused steps would skip the per-step shedding checks the per-step
    path performs.  The Monte-Carlo fast path falls back to the scalar
    loop automatically (subclass ≠ stock continuous batching).
    """

    name = "shedding"
    steady_decode = False
    decode_stable = False

    def __init__(self, max_queue: int = 64, shed_to: Optional[int] = None):
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.max_queue = max_queue
        self.shed_to = max_queue if shed_to is None else shed_to
        if not (0 <= self.shed_to <= max_queue):
            raise ValueError("need 0 <= shed_to <= max_queue")

    def decide(self, replica: ReplicaState, queue: Deque[Request],
               now: float) -> Action:
        if len(queue) > self.max_queue:
            n_drop = len(queue) - self.shed_to
            order = sorted(range(len(queue)),
                           key=lambda i: (queue[i].priority, -i))
            drop = set(order[:n_drop])
            kept = [queue[i] for i in range(len(queue)) if i not in drop]
            shed = tuple(queue[i] for i in sorted(drop))
            queue.clear()
            queue.extend(kept)
            return Shed(shed)
        return super().decide(replica, queue, now)


SCHEDULERS = {
    "continuous": ContinuousBatchingScheduler,
    "bucketed": BucketedPrefillScheduler,
    "static": StaticBatchScheduler,
    "shedding": LoadSheddingScheduler,
}


def make_scheduler(name: str, **kwargs) -> BatchScheduler:
    if name not in SCHEDULERS:
        raise KeyError(f"unknown scheduler {name!r}; "
                       f"available: {sorted(SCHEDULERS)}")
    return SCHEDULERS[name](**kwargs)
