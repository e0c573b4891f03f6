"""Fault-injection serving (PR 9): seeded replica failures, retry /
timeout / backoff, degraded-mode SLOs, and scalar-vs-fused parity.

The load-bearing contract: one seeded fault scenario pushed through
``ServingSimulator``, ``MonteCarloServingSimulator`` and
``CapacityPlanner`` must produce availability / goodput / SLO-under-
failure numbers that are bit-identical (a) across repeated runs and
(b) across the scalar event loop and the fused Monte-Carlo fast path —
fault injection is a *model* feature, not a path-specific behaviour.
"""
import math

import pytest
from _hypothesis_compat import given, settings, st
from _seed_replay import assert_matches_seed_replay

from repro.serve_sim import (SLO, CapacityPlanner,
                             ContinuousBatchingScheduler, FailureModel,
                             LengthDist, LoadSheddingScheduler,
                             MonteCarloServingSimulator, ReplicaFault,
                             RetryPolicy, ServingCostModel, ServingSimulator,
                             compile_faults, poisson_workload,
                             poisson_workload_batch, simulate_serving,
                             trace_workload)

TOY = ServingCostModel(name="toy", prefill_fixed=1e-3, prefill_per_token=2e-5,
                       decode_fixed=2e-3, decode_per_token=5e-4,
                       decode_per_ctx_token=1e-7)

PROMPT = LengthDist(mean=128, cv=0.5)
OUTPUT = LengthDist(mean=32, cv=0.5)

#: the acceptance scenario: 8 replicas under heavy MTBF/MTTR churn with
#: bounded retries and a deadline.
CHURN = FailureModel(mtbf=3.0, mttr=0.5, seed=7, horizon=60.0)
CHURN_RETRY = RetryPolicy(max_attempts=4, backoff=0.02, deadline=30.0)


def toy_poisson(n=200, rate=20.0, seed=0):
    return poisson_workload(rate, n, prompt=PROMPT, output=OUTPUT, seed=seed)


def _report_fields(r):
    """Every cross-path-comparable field of a ServingReport, exactly."""
    return {
        "n_requests": r.n_requests, "duration": r.duration,
        "output_tokens": r.output_tokens, "replica_util": r.replica_util,
        "n_offered": r.n_offered, "n_failures": r.n_failures,
        "n_retries": r.n_retries, "n_abandoned": r.n_abandoned,
        "n_shed": r.n_shed, "availability": r.availability,
        "goodput": r.goodput_rps, "attempts": r.attempt_rps,
        "abandonment": r.abandonment_rate,
        "ttft": (r.ttft.p50, r.ttft.p95, r.ttft.p99, r.ttft.mean),
        "tpot": (r.tpot.p50, r.tpot.p95, r.tpot.p99, r.tpot.mean),
        "e2e": (r.e2e.p50, r.e2e.p95, r.e2e.p99, r.e2e.mean),
        "qd": (r.queue_delay.p50, r.queue_delay.p99),
    }


def _rows(r):
    return [(m.rid, m.replica, m.slot, m.t_admit, m.t_first, m.t_done)
            for m in r.requests]


def _assert_identical(a, b):
    assert _report_fields(a) == _report_fields(b)
    assert _rows(a) == _rows(b)


# ---------------------------------------------------------------------------
# model + schedule compilation
# ---------------------------------------------------------------------------


def test_replica_fault_and_model_validation():
    with pytest.raises(ValueError):
        ReplicaFault(replica=-1, t_fail=0.0, t_repair=1.0)
    with pytest.raises(ValueError):
        ReplicaFault(replica=0, t_fail=2.0, t_repair=1.0)
    with pytest.raises(ValueError):
        FailureModel(mtbf=0.0)
    with pytest.raises(ValueError):
        FailureModel(mode="explode")
    with pytest.raises(ValueError):
        FailureModel(slow_factor=0.5)
    with pytest.raises(ValueError):
        FailureModel(correlated_p=1.5)
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)


def test_failure_windows_deterministic_and_seed_override():
    m = FailureModel(mtbf=5.0, mttr=1.0, seed=42, horizon=100.0)
    assert m.windows(4) == m.windows(4)
    assert m.windows(4) == m.windows(4, seed=42)
    assert m.windows(4) != m.windows(4, seed=43)
    # the Monte-Carlo per-seed tuple re-seeds reproducibly too
    assert m.windows(4, seed=(42, 9)) == m.windows(4, seed=(42, 9))


def test_zone_outages_take_down_whole_zones_when_fully_correlated():
    m = FailureModel(mtbf=2.0, mttr=0.5, seed=1, zone_size=4,
                     correlated_p=1.0, horizon=50.0)
    wins = m.windows(8)
    assert wins
    # every outage window appears once per member of its zone
    by_window = {}
    for w in wins:
        by_window.setdefault((w.t_fail, w.t_repair), []).append(w.replica)
    for members in by_window.values():
        zone = members[0] // 4
        assert sorted(members) == list(range(zone * 4, zone * 4 + 4))


def test_compile_faults_merges_overlaps_and_orders_events():
    cf = compile_faults([ReplicaFault(0, 1.0, 2.0),
                         ReplicaFault(0, 1.5, 3.0),     # overlaps -> merged
                         ReplicaFault(1, 3.0, 4.0)], replicas=2)
    assert [(w.replica, w.t_fail, w.t_repair) for w in cf.windows] == \
        [(0, 1.0, 3.0), (1, 3.0, 4.0)]
    # tie at t=3.0: replica 0's repair (code 0) precedes replica 1's fail
    assert cf.events == [(1.0, 1, 0), (3.0, 0, 0), (3.0, 1, 1), (4.0, 0, 1)]
    assert cf.n_failures(10.0) == 2
    # downtime = 2s (r0) + 1s (r1) over 2 x 10 replica-seconds
    assert cf.availability(10.0, 2) == pytest.approx(1.0 - 3.0 / 20.0)
    assert compile_faults([], replicas=2) is None


# ---------------------------------------------------------------------------
# scalar simulator under faults
# ---------------------------------------------------------------------------


def test_crash_cancels_inflight_and_retries_to_completion():
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(300),
                           replicas=2, slots=8,
                           failures=FailureModel(mtbf=4.0, mttr=0.5, seed=3,
                                                 horizon=30.0),
                           retry=RetryPolicy(max_attempts=8, backoff=0.01))
    base = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(300),
                            replicas=2, slots=8)
    assert rep.n_failures > 0 and rep.n_retries > 0
    assert rep.availability < 1.0
    # generous retry budget: nothing is lost, only delayed
    assert rep.n_abandoned == 0
    assert rep.n_requests == rep.n_offered == 300
    # every request still delivers its tokens; the partial bursts thrown
    # away by crashes are *extra* generated work, never lost work
    assert rep.output_tokens >= base.output_tokens
    assert rep.attempt_rps > rep.goodput_rps            # amplification paid
    assert rep.e2e.p99 >= base.e2e.p99                  # and latency paid
    for m in rep.requests:
        assert m.t_arrive <= m.t_admit <= m.t_first <= m.t_done


#: churn heavy enough that the deadline/attempt budget genuinely binds
ABANDON = FailureModel(mtbf=1.0, mttr=1.0, seed=3, horizon=120.0)
ABANDON_RETRY = RetryPolicy(max_attempts=2, backoff=0.5, deadline=1.0)


def test_accounting_identity_offered_equals_served_plus_dropped():
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(300),
                           replicas=2, slots=8, failures=ABANDON,
                           retry=ABANDON_RETRY)
    assert rep.n_abandoned > 0
    assert rep.n_offered == rep.n_requests + rep.n_abandoned + rep.n_shed
    assert rep.abandonment_rate == pytest.approx(
        (rep.n_abandoned + rep.n_shed) / rep.n_offered)


def test_slow_mode_degrades_latency_not_availability():
    slow = simulate_serving(
        TOY, ContinuousBatchingScheduler, toy_poisson(300), slots=8,
        failures=FailureModel(mtbf=2.0, mttr=1.0, mode="slow",
                              slow_factor=8.0, seed=5, horizon=60.0))
    base = simulate_serving(TOY, ContinuousBatchingScheduler,
                            toy_poisson(300), slots=8)
    assert slow.availability == 1.0          # brownout, not downtime
    assert slow.n_retries == 0 and slow.n_abandoned == 0
    assert slow.n_requests == 300
    assert slow.e2e.mean > base.e2e.mean     # pain shows up in latency
    assert slow.duration > base.duration


def test_single_attempt_policy_abandons_crash_losses():
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(300),
                           replicas=2, slots=8, failures=CHURN,
                           retry=RetryPolicy(max_attempts=1))
    assert rep.n_retries == 0                # no second attempts exist
    assert rep.n_abandoned > 0
    assert rep.n_requests + rep.n_abandoned == rep.n_offered


def test_load_shedding_under_churn_is_priority_aware():
    rows = [(0.001 * i, 64, 24, i % 3) for i in range(240)]

    def sched():
        return LoadSheddingScheduler(max_queue=16, shed_to=8)

    rep = simulate_serving(TOY, sched, trace_workload(rows), slots=4,
                           failures=FailureModel(mtbf=0.2, mttr=0.3, seed=2,
                                                 horizon=5.0),
                           retry=CHURN_RETRY)
    assert rep.n_shed > 0
    assert rep.n_offered == rep.n_requests + rep.n_abandoned + rep.n_shed
    # lowest priority class bears the brunt of the shedding
    served = [m.rid for m in rep.requests]
    shed_prio = [rows[i][3] for i in range(240)
                 if i not in set(served)]
    if shed_prio:
        assert sum(p == 0 for p in shed_prio) >= sum(p == 2
                                                     for p in shed_prio)


def test_seeded_scenario_bit_identical_across_runs():
    def run():
        return simulate_serving(TOY, ContinuousBatchingScheduler,
                                toy_poisson(300, rate=40.0), replicas=8,
                                slots=8, failures=CHURN, retry=CHURN_RETRY)
    _assert_identical(run(), run())


def test_per_request_slo_attainment_counts_dropped_as_misses():
    slo = SLO(ttft_p99=math.inf, tpot_p99=math.inf, e2e_p99=math.inf)
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(300),
                           replicas=2, slots=8, failures=ABANDON,
                           retry=ABANDON_RETRY)
    assert rep.n_abandoned > 0
    # infinitely loose targets: attainment == served fraction exactly
    assert rep.slo_attainment(slo) == pytest.approx(
        rep.n_requests / rep.n_offered)


# ---------------------------------------------------------------------------
# deterministic tie-breaks: graph mode, express lane and fused path agree
# ---------------------------------------------------------------------------


def _metric_rows(rep):
    return [(m.rid, m.replica, m.slot, m.t_admit, m.t_first, m.t_done)
            for m in rep.requests]


def _assert_rows_close(ref, other):
    """Same requests on the same replicas and slots; times to round-off
    (graph mode sums a phase's chunks, the express lane books it whole)."""
    assert len(ref.requests) == len(other.requests)
    for ra, rb in zip(_metric_rows(ref), _metric_rows(other)):
        assert ra[:3] == rb[:3]
        for va, vb in zip(ra[3:], rb[3:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)


def test_tiebreak_fault_at_arrival_timestamp_graph_and_express_lane_agree():
    """A failure event landing exactly on an arrival (and a repair on a
    later arrival) must order identically in TemplateLane graph mode and
    on the express ServiceLane; the graph lane's schedule replays exactly
    on the seed engine."""
    rows = [(0.05 * i, 64, 8) for i in range(40)]
    faults = [ReplicaFault(0, 0.25, 0.50),    # t_fail == arrival of rid 5
              ReplicaFault(1, 0.50, 0.75)]    # fail at repair timestamp

    def sim(phase_tasks):
        return ServingSimulator(TOY, ContinuousBatchingScheduler,
                                trace_workload(rows), replicas=2, slots=4,
                                phase_tasks=phase_tasks,
                                record_events=True, failures=faults,
                                retry=CHURN_RETRY)

    graph_sim = sim(3)
    graph, lane = graph_sim.run(), sim(0).run()
    assert graph.n_failures == lane.n_failures == 2
    assert graph.events == lane.events        # same order of every tie
    for k in ("n_requests", "output_tokens", "n_offered", "n_retries",
              "n_abandoned", "n_shed"):
        assert _report_fields(graph)[k] == _report_fields(lane)[k], k
    assert graph.duration == pytest.approx(lane.duration, rel=1e-12)
    _assert_rows_close(lane, graph)
    assert_matches_seed_replay(graph_sim, graph)


def test_tiebreak_fault_at_decode_completion_scalar_vs_fused():
    """Failure events at decode-step boundaries: the fused Monte-Carlo
    loop and the scalar DES must resolve the fault-vs-completion and
    retry-vs-arrival ties identically (bit-exact rows)."""
    import numpy as np
    from repro.serve_sim.workload import RequestBatch

    # decode steps land on an exact 2ms grid for these lengths
    cost = ServingCostModel(name="grid", prefill_fixed=1e-3,
                            prefill_per_token=0.0, decode_fixed=2e-3,
                            decode_per_token=0.0, decode_per_ctx_token=0.0)
    t = np.array([[0.0, 0.0, 0.004, 0.004, 0.008, 0.05]])
    p = np.full((1, 6), 16, dtype=np.int64)
    o = np.array([[8, 4, 6, 2, 5, 3]], dtype=np.int64)
    batch = RequestBatch(t_arrive=t, prompt=p, output=o,
                         seeds=(0,), name="grid")
    faults = [ReplicaFault(0, 0.005, 0.009),   # fail on a decode boundary
              ReplicaFault(0, 0.013, 0.017)]
    retry = RetryPolicy(max_attempts=6, backoff=0.004, backoff_factor=1.0,
                        jitter=0.0)            # retries land on the grid too
    for replicas in (1, 2):
        fast = MonteCarloServingSimulator(
            cost, ContinuousBatchingScheduler, batch, replicas=replicas,
            slots=2, failures=faults, retry=retry)
        assert fast.fast_path
        slow = MonteCarloServingSimulator(
            cost, ContinuousBatchingScheduler, batch, replicas=replicas,
            slots=2, failures=faults, retry=retry)
        slow.fast_path = False
        a, b = fast.run(), slow.run()
        _assert_identical(a.reports[0], b.reports[0])
        assert a.reports[0].n_failures == 2


# ---------------------------------------------------------------------------
# rollback under failure: crash mid-decode-burst
# ---------------------------------------------------------------------------


def _burst_workload():
    # few wide requests -> long fused decode bursts to crash into
    rows = [(0.0, 64, 40), (0.0, 64, 40), (0.001, 64, 40), (0.001, 64, 40)]
    return trace_workload(rows)


_MID_BURST = [ReplicaFault(0, 0.031, 0.05)]   # strictly inside a burst


def test_crash_mid_burst_lane_mode_matches_per_step_golden():
    """A replica failing mid-decode-burst forces a leap rollback; the
    leaping lane run must match the per-step (record_events=True) golden
    run to round-off, with exact fault counters."""
    leap = ServingSimulator(TOY, ContinuousBatchingScheduler,
                            _burst_workload(), replicas=1, slots=4,
                            failures=_MID_BURST, retry=CHURN_RETRY).run()
    golden = ServingSimulator(TOY, ContinuousBatchingScheduler,
                              _burst_workload(), replicas=1, slots=4,
                              record_events=True, failures=_MID_BURST,
                              retry=CHURN_RETRY).run()
    assert leap.n_failures == golden.n_failures == 1
    assert leap.n_retries == golden.n_retries > 0
    assert leap.n_requests == golden.n_requests == 4
    assert leap.duration == pytest.approx(golden.duration, rel=1e-12)
    for ra, rb in zip(_metric_rows(leap), _metric_rows(golden)):
        assert ra[:3] == rb[:3]
        for va, vb in zip(ra[3:], rb[3:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)


def test_crash_mid_burst_graph_mode_matches_per_step():
    """The graph-mode mirror of the lane-mode test above: a crash inside
    a fused decode (one TemplateLane phase) must commit the steps before
    it and match the per-step golden run to round-off, with exact fault
    counters; both lanes' surviving phases replay on the seed engine."""
    def sim(record_events):
        return ServingSimulator(TOY, ContinuousBatchingScheduler,
                                _burst_workload(), replicas=1, slots=4,
                                phase_tasks=3, record_events=record_events,
                                failures=_MID_BURST, retry=CHURN_RETRY)
    leap_sim, golden_sim = sim(False), sim(True)
    leap, golden = leap_sim.run(), golden_sim.run()
    assert leap.n_failures == golden.n_failures == 1
    assert leap.n_retries == golden.n_retries > 0
    assert leap.n_requests == golden.n_requests == 4
    assert leap.output_tokens == golden.output_tokens
    assert leap.duration == pytest.approx(golden.duration, rel=1e-12)
    _assert_rows_close(golden, leap)
    assert_matches_seed_replay(golden_sim, golden)
    assert_matches_seed_replay(leap_sim, leap)


# ---------------------------------------------------------------------------
# Monte-Carlo: per-seed failure draws, scalar-vs-fused bit parity, CI bands
# ---------------------------------------------------------------------------

_SCENARIOS = [
    ("churn", CHURN, CHURN_RETRY),
    ("abandon", FailureModel(mtbf=1.0, mttr=1.0, seed=3, horizon=120.0),
     RetryPolicy(max_attempts=2, backoff=0.5, deadline=2.0)),
    ("slow", FailureModel(mtbf=4.0, mttr=0.8, seed=11, mode="slow",
                          slow_factor=6.0, horizon=60.0), None),
    ("zone", FailureModel(mtbf=2.0, mttr=0.6, seed=5, zone_size=4,
                          correlated_p=0.5, horizon=60.0), CHURN_RETRY),
]


@pytest.mark.parametrize("name,failures,retry", _SCENARIOS,
                         ids=[s[0] for s in _SCENARIOS])
def test_scalar_vs_fused_bit_parity_per_seed(name, failures, retry):
    batch = poisson_workload_batch(40.0, 200, prompt=PROMPT, output=OUTPUT,
                                   seeds=8)
    fast = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler,
                                      batch, replicas=8, slots=8,
                                      failures=failures, retry=retry)
    assert fast.fast_path
    slow = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler,
                                      batch, replicas=8, slots=8,
                                      failures=failures, retry=retry)
    slow.fast_path = False
    a, b = fast.run(), slow.run()
    for ra, rb in zip(a.reports, b.reports):
        _assert_identical(ra, rb)
    assert a.stats == b.stats


def test_per_seed_failure_draws_differ_but_reproduce():
    batch = poisson_workload_batch(40.0, 150, prompt=PROMPT, output=OUTPUT,
                                   seeds=16)
    mc = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler, batch,
                                    replicas=8, slots=8, failures=CHURN,
                                    retry=CHURN_RETRY)
    a = mc.run()
    avail = [r.availability for r in a.reports]
    assert len(set(avail)) > 1           # independent per-seed schedules
    st_ = a.stat("availability")
    assert 0.0 < st_.ci_lo <= st_.mean <= st_.ci_hi <= 1.0
    assert a.stat("abandonment_rate").mean >= 0.0
    # bit-identical on a repeated run, fused or scalar
    b = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler, batch,
                                   replicas=8, slots=8, failures=CHURN,
                                   retry=CHURN_RETRY).run()
    assert [_report_fields(r) for r in a.reports] == \
        [_report_fields(r) for r in b.reports]
    assert a.stats == b.stats
    assert "avail" in a.summary()


def test_planner_sizes_n_plus_one_redundancy_under_faults():
    """The same SLO needs more replicas once replicas churn: the planner
    threads the fault profile into every probe and decides on the
    availability CI."""
    def factory():
        return poisson_workload_batch(60.0, 120, prompt=PROMPT,
                                      output=OUTPUT, seeds=8)

    # note the availability floor is a *gate*, not the sizing driver: the
    # per-replica up-fraction barely moves with fleet size, so redundancy
    # is bought by the latency target degrading when capacity churns away
    slo = SLO(e2e_p99=0.5, availability=0.5)
    faulty = CapacityPlanner(TOY, ContinuousBatchingScheduler, factory, slo,
                             num_seeds=8,
                             failures=FailureModel(mtbf=8.0, mttr=4.0,
                                                   seed=13, horizon=30.0),
                             retry=CHURN_RETRY)
    clean = CapacityPlanner(TOY, ContinuousBatchingScheduler, factory, slo,
                            num_seeds=8)
    pf, pc = faulty.plan("replicas", cap=16), clean.plan("replicas", cap=16)
    assert pc.feasible and pf.feasible
    assert pf.value > pc.value                   # churn costs capacity
    assert pf.report.stat("availability").ci_lo >= 0.5
    assert pf.report.stat("e2e_p99").ci_hi <= 0.5
    # deterministic: the same planning run reproduces bit-identically
    pf2 = CapacityPlanner(TOY, ContinuousBatchingScheduler, factory, slo,
                          num_seeds=8,
                          failures=FailureModel(mtbf=8.0, mttr=4.0,
                                                seed=13, horizon=30.0),
                          retry=CHURN_RETRY).plan("replicas", cap=16)
    assert pf2.value == pf.value and pf2.probes == pf.probes
    assert pf2.report.stats == pf.report.stats


def test_slo_availability_floor_gates_single_reports():
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(200),
                           replicas=2, slots=8, failures=CHURN,
                           retry=CHURN_RETRY)
    assert rep.availability < 1.0
    assert SLO(availability=rep.availability - 1e-9).satisfied_by(rep)
    assert not SLO(availability=1.0).satisfied_by(rep)
    assert "avail" in str(SLO(availability=0.999))


# ---------------------------------------------------------------------------
# observability: failure/retry/shed events as probe counter tracks
# ---------------------------------------------------------------------------


def test_fault_counters_and_events_match_report_and_paths():
    from repro.obs.probe import Probe

    batch = poisson_workload_batch(40.0, 150, prompt=PROMPT, output=OUTPUT,
                                   seeds=2)

    def counters(force_scalar):
        prb = Probe("faults", sample_every=4)
        mc = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler,
                                        batch, replicas=4, slots=8,
                                        probe=prb, failures=CHURN,
                                        retry=CHURN_RETRY)
        if force_scalar:
            mc.fast_path = False
        rep = mc.run()
        out = {}
        for k, child in prb.children.items():
            m = child.to_metrics()["counters"]
            ev = child.all_events()
            out[k] = ({n: v for n, v in m.items()
                       if n.split("/")[-1] in ("failures", "retries",
                                               "abandoned", "shed")},
                      [e for e in ev if e[0].startswith("replica_")])
        return rep, out

    rep_f, fused = counters(False)
    rep_s, scalar = counters(True)
    assert fused == scalar                       # events + finals bit-equal
    for k in range(2):
        child = fused[f"seed{batch.seeds[k]}"]
        r = rep_f.reports[k]
        finals = {n.split("/")[-1]: v for n, v in child[0].items()}
        # the counter tracks fail *events processed* over the whole fault
        # schedule; the report counts windows begun by the makespan —
        # the schedule can outlive the traffic, never the reverse
        assert finals["failures"] >= r.n_failures > 0
        assert finals["retries"] == r.n_retries
        assert finals["abandoned"] == r.n_abandoned
        assert finals["shed"] == r.n_shed
        assert any(e[0] == "replica_fail" for e in child[1])
        assert any(e[0] == "replica_repair" for e in child[1])


# ---------------------------------------------------------------------------
# property: availability/goodput bit-identical across paths, any seed
# ---------------------------------------------------------------------------


def _paths_agree(seed: int) -> None:
    batch = poisson_workload_batch(35.0, 80, prompt=PROMPT, output=OUTPUT,
                                   seeds=(seed,))
    kw = dict(replicas=4, slots=8,
              failures=FailureModel(mtbf=2.0, mttr=0.5, seed=seed,
                                    horizon=30.0),
              retry=CHURN_RETRY)
    fast = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler,
                                      batch, **kw)
    assert fast.fast_path
    slow = MonteCarloServingSimulator(TOY, ContinuousBatchingScheduler,
                                      batch, **kw)
    slow.fast_path = False
    ra, rb = fast.run().reports[0], slow.run().reports[0]
    assert ra.availability == rb.availability
    assert ra.goodput_rps == rb.goodput_rps
    assert ra.attempt_rps == rb.attempt_rps
    assert ra.abandonment_rate == rb.abandonment_rate


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_property_availability_goodput_path_invariant(seed):
    _paths_agree(seed)


def test_sweep_availability_goodput_path_invariant():
    """Deterministic fallback for the hypothesis property above (the dev
    extra may be absent): a fixed seed sweep checks the same invariant."""
    for seed in (0, 1, 7, 123, 4096):
        _paths_agree(seed)


# ---------------------------------------------------------------------------
# input validation: NaN/inf guards (PR 10)
# ---------------------------------------------------------------------------


def test_failure_model_rejects_non_finite_parameters():
    nan, inf = math.nan, math.inf
    for kw in ({"mtbf": nan}, {"mtbf": inf}, {"mttr": nan}, {"mttr": inf},
               {"mode": "slow", "slow_factor": nan},
               {"mode": "slow", "slow_factor": inf},
               {"horizon": nan}, {"horizon": inf},
               {"zone_size": -1}, {"zone_size": 1.5},
               {"correlated_p": nan}):
        with pytest.raises(ValueError):
            FailureModel(**kw)


def test_retry_policy_rejects_non_finite_parameters():
    nan, inf = math.nan, math.inf
    for kw in ({"backoff": nan}, {"backoff": inf},
               {"backoff_factor": nan}, {"backoff_factor": inf},
               {"jitter": nan}, {"jitter": inf}, {"deadline": nan}):
        with pytest.raises(ValueError):
            RetryPolicy(**kw)
    # an unbounded deadline is the documented default and stays legal
    assert RetryPolicy(deadline=inf).deadline == inf


def test_replica_fault_rejects_nan_window():
    for t_fail, t_repair in ((math.nan, 1.0), (0.0, math.nan)):
        with pytest.raises(ValueError):
            ReplicaFault(0, t_fail, t_repair)


# ---------------------------------------------------------------------------
# shed accounting audit: n_shed == per-priority breakdown == probe counter
# ---------------------------------------------------------------------------


def test_shed_accounting_audit_by_priority_and_probe():
    from repro.obs import Probe
    rows = [(0.001 * i, 64, 24, i % 3) for i in range(240)]
    p = Probe("shed-audit")
    rep = simulate_serving(
        TOY, lambda: LoadSheddingScheduler(max_queue=16, shed_to=8),
        trace_workload(rows), slots=4, probe=p,
        failures=FailureModel(mtbf=0.2, mttr=0.3, seed=2, horizon=5.0),
        retry=CHURN_RETRY)
    assert rep.n_shed > 0
    # the audit identity: the priority breakdown partitions n_shed exactly
    assert sum(rep.shed_by_priority.values()) == rep.n_shed
    assert set(rep.shed_by_priority) <= {0, 1, 2}
    assert all(v > 0 for v in rep.shed_by_priority.values())
    # the observability counter is the same ledger, not a parallel one
    assert p.to_metrics()["counters"]["serve/shed"] == rep.n_shed
    assert rep.n_offered == rep.n_requests + rep.n_abandoned + rep.n_shed


# ---------------------------------------------------------------------------
# property: fault schedules and retry jitter are seed-deterministic
# ---------------------------------------------------------------------------


def _schedule_of(fm, replicas, seed=None):
    cf = compile_faults(fm, replicas, seed=seed)
    return None if cf is None else (cf.events, cf.mode, cf.slow_factor)


def _check_fault_schedule_deterministic(seed, replicas, zone, corr):
    fm = FailureModel(mtbf=2.0, mttr=0.5, seed=seed, horizon=20.0,
                      zone_size=zone, correlated_p=corr)
    base = _schedule_of(fm, replicas)
    assert base == _schedule_of(fm, replicas)
    # per-scenario seed override reproduces too (the Monte-Carlo contract)
    over = _schedule_of(fm, replicas, seed=(seed, 1))
    assert over == _schedule_of(fm, replicas, seed=(seed, 1))
    if base is not None:
        ev = base[0]
        assert ev == sorted(ev)                    # time-ordered
        assert all(0 <= r < replicas for _, _, r in ev)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 16), st.integers(1, 12), st.integers(0, 4),
       st.floats(0.0, 1.0))
def test_property_fault_schedule_deterministic(seed, replicas, zone, corr):
    _check_fault_schedule_deterministic(seed, replicas, zone, corr)


def test_sweep_fault_schedule_deterministic():
    """Deterministic fallback for the hypothesis property above."""
    for seed in (0, 3, 911):
        for zone, corr in ((0, 0.0), (2, 0.5), (3, 1.0)):
            _check_fault_schedule_deterministic(seed, 8, zone, corr)


def _jitter_stream_reproduces(seed: int) -> None:
    def run():
        return simulate_serving(
            TOY, ContinuousBatchingScheduler,
            toy_poisson(120, rate=30.0, seed=seed), replicas=4, slots=8,
            failures=FailureModel(mtbf=1.5, mttr=0.4, seed=seed,
                                  horizon=20.0),
            retry=RetryPolicy(max_attempts=4, jitter=0.9))
    _assert_identical(run(), run())


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_property_retry_jitter_stream_reproducible(seed):
    _jitter_stream_reproduces(seed)


def test_sweep_retry_jitter_stream_reproducible():
    for seed in (1, 42, 2026):
        _jitter_stream_reproduces(seed)
