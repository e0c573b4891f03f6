"""Virtual serving subsystem: workload generators, cost models, schedulers,
the traffic-driven simulator, capacity planning — and parity between the
virtual continuous-batching scheduler and the real ``BatchedServer`` loop."""
import json

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _seed_replay import assert_matches_seed_replay

from repro.core.sim.engine import DynamicSimulator, ResourceSpec, Task
from repro.core.sim.trace import serving_chrome_trace
from repro.serve_sim.scheduler import Decode, Prefill
from repro.serve_sim import (SLO, BucketedPrefillScheduler, CapacityPlanner,
                             ClosedLoopWorkload, ContinuousBatchingScheduler,
                             LengthDist, ServingCostModel,
                             ServingCostModelBuilder, ServingSimulator,
                             StaticBatchScheduler, bursty_workload,
                             poisson_workload, simulate_serving,
                             trace_workload)

TOY = ServingCostModel(name="toy", prefill_fixed=1e-3, prefill_per_token=2e-5,
                       decode_fixed=2e-3, decode_per_token=5e-4,
                       decode_per_ctx_token=1e-7)


def toy_poisson(n=200, rate=20.0, seed=0):
    return poisson_workload(rate, n, prompt=LengthDist(mean=128, cv=0.5),
                            output=LengthDist(mean=32, cv=0.5), seed=seed)


# ---------------------------------------------------------------------------
# engine: dynamic event injection
# ---------------------------------------------------------------------------


def test_engine_timed_callback_injects_tasks():
    sim = DynamicSimulator(resources={"r": ResourceSpec("r")})
    sim.at(1.0, lambda: sim.inject(Task(0, "late", "L", "r", 2.0)))
    res = sim.run()
    rec = res.records[0]
    assert rec.start == pytest.approx(1.0)
    assert rec.end == pytest.approx(3.0)
    assert res.makespan == pytest.approx(3.0)


def test_engine_injected_task_waits_for_inflight_dep():
    sim = DynamicSimulator([Task(0, "a", "L", "r", 2.0)])
    sim.at(0.5, lambda: sim.inject(Task(1, "b", "L", "r", 1.0, deps=(0,))))
    res = sim.run()
    recs = {r.task.tid: r for r in res.records}
    assert recs[1].start == pytest.approx(2.0)   # blocked on in-flight dep


def test_engine_on_complete_chains_tasks():
    done = []

    def hook(task, now):
        done.append((task.tid, now))
        if task.tid < 3:
            sim.inject(Task(task.tid + 1, f"t{task.tid + 1}", "L", "r", 1.0))

    sim = DynamicSimulator([Task(0, "t0", "L", "r", 1.0)], on_complete=hook)
    res = sim.run()
    assert [d[0] for d in done] == [0, 1, 2, 3]
    assert res.makespan == pytest.approx(4.0)


def test_engine_next_task_id_monotone():
    sim = DynamicSimulator([Task(5, "a", "L", "r", 1.0)])
    assert sim.next_task_id() == 6
    sim.inject(Task(6, "b", "L", "r", 1.0))
    assert sim.next_task_id() == 7


def test_engine_rejects_past_callback():
    sim = DynamicSimulator()
    with pytest.raises(ValueError):
        sim.at(-1.0, lambda: None)


# ---------------------------------------------------------------------------
# workload generators (satellite: seeded determinism, rate, length sanity)
# ---------------------------------------------------------------------------


def test_poisson_seeded_determinism():
    a = poisson_workload(10.0, 100, seed=7).requests
    b = poisson_workload(10.0, 100, seed=7).requests
    c = poisson_workload(10.0, 100, seed=8).requests
    assert a == b
    assert a != c


def test_poisson_empirical_rate_close():
    wl = poisson_workload(50.0, 5000, seed=0)
    assert wl.offered_rate == pytest.approx(50.0, rel=0.1)
    times = [r.t_arrive for r in wl.requests]
    assert times == sorted(times)
    assert all(t > 0 for t in times)


def test_bursty_deterministic_and_monotone():
    a = bursty_workload(5.0, 50.0, 300, mean_dwell=2.0, seed=3).requests
    b = bursty_workload(5.0, 50.0, 300, mean_dwell=2.0, seed=3).requests
    assert a == b
    times = [r.t_arrive for r in a]
    assert times == sorted(times)
    # empirical rate lands between the two phase rates
    rate = (len(times) - 1) / (times[-1] - times[0])
    assert 5.0 < rate < 50.0


def test_length_dist_sanity():
    rng = np.random.default_rng(0)
    ln = LengthDist(kind="lognormal", mean=256, cv=0.5, lo=16, hi=1024)
    x = ln.sample(rng, 4000)
    assert x.min() >= 16 and x.max() <= 1024
    assert np.mean(x) == pytest.approx(256, rel=0.1)
    fx = LengthDist(kind="fixed", mean=64, lo=64, hi=64).sample(rng, 10)
    assert (fx == 64).all()
    un = LengthDist(kind="uniform", mean=100, cv=0.5, lo=1).sample(rng, 4000)
    assert 50 <= un.min() and un.max() <= 150
    with pytest.raises(ValueError):
        LengthDist(kind="weird")


def test_trace_workload_sorts_and_preserves_rows():
    wl = trace_workload([(2.0, 10, 5), (1.0, 20, 6), (3.0, 30, 7)])
    assert [r.t_arrive for r in wl.requests] == [1.0, 2.0, 3.0]
    assert [r.prompt_tokens for r in wl.requests] == [20, 10, 30]
    assert [r.rid for r in wl.requests] == [0, 1, 2]


def test_trace_workload_guards_malformed_traces():
    """Empty or malformed traces raise immediately with the offending
    row — a bad production log must not become negative inter-arrivals
    or a simulation that never terminates."""
    from repro.serve_sim import trace_workload_batch

    with pytest.raises(ValueError, match="empty"):
        trace_workload([])
    with pytest.raises(ValueError, match="arrival"):
        trace_workload([(float("nan"), 10, 5)])
    with pytest.raises(ValueError, match="arrival"):
        trace_workload([(0.0, 10, 5), (-1.0, 20, 6)])
    with pytest.raises(ValueError, match="arrival"):
        trace_workload([(float("inf"), 10, 5)])
    with pytest.raises(ValueError):
        trace_workload([(0.0, -1, 5)])           # negative prompt
    with pytest.raises(ValueError):
        trace_workload([(0.0, 10, 0)])           # zero output tokens
    with pytest.raises(ValueError, match="fields"):
        trace_workload([(0.0, 10)])
    # the batch variant applies the same guards
    with pytest.raises(ValueError, match="empty"):
        trace_workload_batch([], seeds=2)
    with pytest.raises(ValueError, match="arrival"):
        trace_workload_batch([(-2.0, 10, 5)], seeds=2)


def test_closed_loop_issues_bounded_requests():
    wl = ClosedLoopWorkload(n_users=4, requests_per_user=3, think_time=0.1,
                            seed=1)
    first = wl.initial()
    assert len(first) == 4
    assert wl.n_requests == 12
    # each completion may spawn at most requests_per_user per user
    follow = wl.on_complete(first[0], t_done=5.0)
    assert follow is not None and follow.user == first[0].user
    assert follow.t_arrive > 5.0
    wl.on_complete(follow, 6.0)
    assert wl.on_complete(follow, 7.0) is None   # budget exhausted


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.floats(0.5, 100.0))
def test_poisson_property_deterministic_and_positive(seed, rate):
    a = poisson_workload(rate, 50, seed=seed).requests
    b = poisson_workload(rate, 50, seed=seed).requests
    assert a == b
    assert all(r.prompt_tokens >= 1 and r.output_tokens >= 1 for r in a)
    gaps = np.diff([0.0] + [r.t_arrive for r in a])
    assert (gaps >= 0).all()


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_cost_model_monotone():
    assert TOY.prefill_time(512) > TOY.prefill_time(16)
    assert TOY.decode_step_time(8, 4096) > TOY.decode_step_time(8, 128)
    assert TOY.decode_step_time(8, 128) > TOY.decode_step_time(1, 128)
    assert TOY.decode_step_time(0, 0) == 0.0


def test_cost_builder_from_compiled_graphs():
    from repro.core.avsm.model import annotate_system
    from repro.core.config import get_arch
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.core.taskgraph.builders import ShardPlan

    cfg = get_arch("qwen1.5-0.5b").smoke
    base = SystemDescription(name="chip", chip=tpu_v5e_chip(), torus=())
    builder = ServingCostModelBuilder(cfg, shard=ShardPlan(data=1, model=1),
                                      calib_batches=(1, 4),
                                      calib_ctx=(128, 512))
    cost = builder.model_for(base)
    assert cost.prefill_per_token > 0
    assert cost.decode_fixed > 0 or cost.decode_per_token > 0
    n_compiles = builder.stats["compiles"]
    # a physical variant re-annotates the cached graphs, no recompiles
    fast = builder.model_for(annotate_system(base, mem_bandwidth=1638e9))
    assert builder.stats["compiles"] == n_compiles
    assert builder.stats["reannotations"] > 0
    # double memory bandwidth must not slow serving down
    assert fast.decode_step_time(4, 512) <= cost.decode_step_time(4, 512)


def _profiled_cost(phase_chunks=4):
    from repro.core.config import get_arch
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.core.taskgraph.builders import ShardPlan

    cfg = get_arch("qwen1.5-0.5b").smoke
    base = SystemDescription(name="chip", chip=tpu_v5e_chip(), torus=())
    builder = ServingCostModelBuilder(cfg, shard=ShardPlan(data=1, model=1),
                                      calib_batches=(1, 4),
                                      calib_ctx=(128, 512))
    return builder.model_for(base, phase_chunks=phase_chunks)


def test_compiled_phase_profiles_from_builder():
    """``model_for(system, phase_chunks=N)`` derives per-chunk profiles
    from the compiled calibration graphs: N chunks, compute shares
    summing to 1, and a chunked phase whose total duration is exactly
    the phase cost (compiled-chunk exactness vs the affine split)."""
    cost = _profiled_cost(phase_chunks=4)
    for profile in (cost.prefill_profile, cost.decode_profile):
        assert profile is not None
        assert len(profile.compute) == len(profile.dma) == 4
        assert sum(profile.compute) == pytest.approx(1.0, rel=1e-12)
        assert all(f >= 0.0 for f in profile.compute + profile.dma)
        # exact total: the last chunk absorbs the accumulation residue
        for dur in (1.0, 0.0137, 3.14159e-3):
            comp, dma = profile.chunk_durations(dur)
            total = 0.0
            for d in comp:
                total += d
            assert total == dur
            assert len(dma) == 4
    # compiled graphs move real bytes: some chunk overlaps a DMA
    assert sum(cost.prefill_profile.dma) > 0.0
    # default keeps the affine-only model
    assert _profiled_cost(phase_chunks=0).decode_profile is None


def test_profile_from_graph_groups_real_tasks():
    """Chunking preserves the compiled graph's totals: compute and DMA
    time land in chunks without loss, in compiled task order."""
    from repro.serve_sim.cost import profile_from_graph

    for n in (1, 2, 5):
        profile = _profiled_cost(phase_chunks=n).decode_profile
        assert len(profile.compute) == n
        assert sum(profile.compute) == pytest.approx(1.0, rel=1e-12)


def test_profiled_graph_mode_matches_affine_metrics():
    """Compiled-chunk durations re-shape *intra-phase* structure only:
    phase totals are unchanged, so serving metrics match the equal-split
    graph mode to round-off, while the lane's schedule stays
    bit-identical to the seed engine running the same phases."""
    cost = _profiled_cost(phase_chunks=3)
    plain = ServingCostModel(
        name="plain", prefill_fixed=cost.prefill_fixed,
        prefill_per_token=cost.prefill_per_token,
        decode_fixed=cost.decode_fixed,
        decode_per_token=cost.decode_per_token,
        decode_per_ctx_token=cost.decode_per_ctx_token)
    prof_sim = ServingSimulator(cost, ContinuousBatchingScheduler,
                                toy_poisson(150), replicas=2, slots=4,
                                phase_tasks=3, record_events=True)
    prof = prof_sim.run()
    affine = ServingSimulator(plain, ContinuousBatchingScheduler,
                              toy_poisson(150), replicas=2, slots=4,
                              phase_tasks=3, record_events=True).run()
    for ra, rb in zip(_metric_rows(affine), _metric_rows(prof)):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)
    # profile-carrying phases replay exactly on the seed engine
    assert_matches_seed_replay(prof_sim, prof)
    # and the compiled structure shows up: KV DMAs have real durations
    kv = [r for r in prof.sim_result.records if r.task.kind == "dma"]
    assert kv and any(r.end > r.start for r in kv)


# ---------------------------------------------------------------------------
# serving simulator
# ---------------------------------------------------------------------------


def test_all_requests_complete_and_conserve_tokens():
    wl = toy_poisson(300, seed=2)
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, wl, slots=8)
    assert rep.n_requests == 300
    assert rep.output_tokens == sum(r.output_tokens for r in wl.requests)
    for m in rep.requests:
        assert m.t_admit >= m.t_arrive - 1e-12
        assert m.t_first >= m.t_admit
        assert m.t_done >= m.t_first
    assert 0.0 < rep.replica_util <= 1.0 + 1e-9


def test_simulator_deterministic():
    a = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(), slots=4)
    b = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(), slots=4)
    assert a.duration == b.duration
    assert a.ttft.p99 == b.ttft.p99
    assert [m.t_done for m in a.requests] == [m.t_done for m in b.requests]


def test_replica_tasks_never_overlap():
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(100),
                           replicas=2, slots=4)
    by_res = {}
    for r in rep.sim_result.records:
        by_res.setdefault(r.task.resource, []).append((r.start, r.end))
    assert set(by_res) == {"replica0", "replica1"}
    for spans in by_res.values():
        spans.sort()
        for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
            assert s2 >= e1 - 1e-9


def test_static_batching_is_no_faster_than_continuous():
    # all requests at t=0, mixed output lengths: static holds finished
    # slots until the batch drains, continuous refills them
    rows = [(0.0, 64, 8 + 4 * (i % 12)) for i in range(48)]
    cont = simulate_serving(TOY, ContinuousBatchingScheduler,
                            trace_workload(rows), slots=8)
    stat = simulate_serving(TOY, lambda: StaticBatchScheduler(8, 0.1),
                            trace_workload(rows), slots=8)
    assert cont.n_requests == stat.n_requests == 48
    assert stat.duration >= cont.duration - 1e-9
    assert stat.ttft.p99 >= cont.ttft.p99 - 1e-9


def test_bucketed_prefill_pays_padding():
    rows = [(0.0, 65, 4) for _ in range(8)]    # 65 pads to 128
    bucketed = simulate_serving(TOY, lambda: BucketedPrefillScheduler(128),
                                trace_workload(rows), slots=8)
    exact = simulate_serving(TOY, ContinuousBatchingScheduler,
                             trace_workload(rows), slots=8)
    assert bucketed.n_requests == exact.n_requests == 8
    # bucketed prefill does strictly more prefill work
    assert bucketed.ttft.mean > exact.ttft.mean - 1e-12


def test_more_replicas_cut_tail_latency():
    wl = lambda: toy_poisson(400, rate=30.0, seed=5)   # noqa: E731
    one = simulate_serving(TOY, ContinuousBatchingScheduler, wl(), replicas=1,
                           slots=8)
    four = simulate_serving(TOY, ContinuousBatchingScheduler, wl(), replicas=4,
                            slots=8)
    assert four.ttft.p99 < one.ttft.p99


def test_closed_loop_serving_completes():
    wl = ClosedLoopWorkload(n_users=6, requests_per_user=5, think_time=0.05,
                            prompt=LengthDist(mean=64), output=LengthDist(mean=16),
                            seed=9)
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, wl, slots=4)
    assert rep.n_requests == 30


# ---------------------------------------------------------------------------
# task-graph mode: the seed engine, the express lane, and per-step runs
# ---------------------------------------------------------------------------


def _metric_rows(rep):
    return [(m.rid, m.t_admit, m.t_first, m.t_done) for m in rep.requests]


def _assert_metric_rows_close(ref, other, rel):
    assert other.n_requests == ref.n_requests
    assert other.output_tokens == ref.output_tokens
    for ra, rb in zip(_metric_rows(ref), _metric_rows(other)):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=rel, abs=1e-12)


class PerStepContinuous(ContinuousBatchingScheduler):
    """Continuous batching without the speculative-leap contract: decode
    steps fuse only while admission is blocked, so a batch with a free
    slot runs step by step — the baseline the speculative leap is held
    to."""

    decode_stable = False


@pytest.mark.parametrize("chunks", [1, 3])
def test_graph_mode_matches_seed_engine_replay_exactly(chunks):
    """Per-step task-graph mode (record_events disables leaping): the
    TemplateLane's deferred schedule must equal the seed engine running
    the same phases task by task (bit-identical — same arithmetic, same
    event order), and the express lane at the same phase totals must make
    the same decisions, with metrics equal to round-off."""
    sim = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(250),
                           replicas=2, slots=4, phase_tasks=chunks,
                           record_events=True)
    graph = sim.run()
    assert_matches_seed_replay(sim, graph)
    lane = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(250),
                            replicas=2, slots=4, record_events=True).run()
    assert graph.events == lane.events
    _assert_metric_rows_close(lane, graph, rel=1e-9)


def test_graph_mode_blocked_fusion_matches_seed_engine_exactly():
    """Blocked (non-speculative) decode leaps: hold_finished static
    batching never takes the speculative path, so every fused step is a
    plain TemplateLane phase that the seed engine replays bit for bit;
    the express lane fuses the same steps."""
    sim = ServingSimulator(TOY, StaticBatchScheduler, toy_poisson(250),
                           replicas=2, slots=4, phase_tasks=3)
    graph = sim.run()
    assert_matches_seed_replay(sim, graph)
    lane = ServingSimulator(TOY, StaticBatchScheduler, toy_poisson(250),
                            replicas=2, slots=4).run()
    _assert_metric_rows_close(lane, graph, rel=1e-9)


@pytest.mark.parametrize("chunks", [1, 4])
def test_graph_mode_speculative_leap_matches_per_step(chunks):
    """Graph-mode speculative leaps (TemplateLane bursts + rollback)
    against the same graph mode running those batches per step: metrics
    must agree to float round-off — the fused per-step boundaries use
    the same arithmetic, accumulated in one pass.  The burst steps
    replay on the seed engine to the same round-off."""
    sim = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(250),
                           replicas=2, slots=4, phase_tasks=chunks)
    leap = sim.run()
    per_step = ServingSimulator(TOY, PerStepContinuous, toy_poisson(250),
                                replicas=2, slots=4,
                                phase_tasks=chunks).run()
    _assert_metric_rows_close(per_step, leap, rel=1e-12)
    assert_matches_seed_replay(sim, leap, exact=False)


def test_graph_mode_scripted_rollback_matches_per_step():
    """Scripted mid-leap interventions in graph mode: arrivals land
    while a TemplateLane burst is in flight, forcing truncation back to
    a step boundary and per-step replay; the same policy without the
    speculative contract, run per step, is the ground truth."""
    fast = simulate_serving(TOY, lambda: ScriptedInterveningScheduler(32),
                            _light_traffic(), slots=8, phase_tasks=4)
    per_step = simulate_serving(TOY, lambda: PerStepScripted(32),
                                _light_traffic(), slots=8, phase_tasks=4)
    _assert_metric_rows_close(per_step, fast, rel=1e-12)
    # fusion must actually engage: far fewer materialized decode chunks
    fast_decode = sum(1 for r in fast.sim_result.records
                      if r.task.kind == "decode")
    step_decode = sum(1 for r in per_step.sim_result.records
                      if r.task.kind == "decode")
    assert fast_decode == step_decode     # every truncated step replays


def test_graph_mode_burst_truncation_white_box():
    """An admission on replica 0 must truncate replica 1's in-flight
    TemplateLane burst at the snapshot boundary: entries shrink, the
    stale completion event is epoch-invalidated, and the truncated end
    matches the boundary (the graph-mode mirror of the express-lane
    sibling-admission test)."""
    wl = toy_poisson(4)
    sim = ServingSimulator(TOY, ContinuousBatchingScheduler, wl,
                           replicas=2, slots=2, phase_tasks=2)
    lane1 = sim._lanes[1]
    tpl = sim._template(1, "decode")
    bounds = [round(0.1 * i, 10) for i in range(1, 11)]
    lane1.submit_burst(tpl, bounds, lambda now: None)
    assert lane1.end == pytest.approx(1.0)
    sim._leap[1] = (bounds, 2)
    sim._decode_k[1] = 10
    req = wl.requests[0]
    sim._start_prefill(sim.replicas[0], Prefill((req,), req.prompt_tokens),
                       now=0.25)
    assert sim._leap[1] is None                    # disarmed
    assert sim._decode_k[1] == 3                   # boundary 0.3 = step 3
    assert lane1.end == pytest.approx(0.3)         # burst truncated
    assert lane1.epoch == 1                        # stale completion voided
    assert len(lane1.entries[-1][3]) == 3          # 3 snapshot steps kept


def test_graph_mode_matches_express_lane_metrics():
    """Chunked phase graphs exact-split the phase cost, so serving
    metrics equal the ServiceLane express path to float round-off."""
    lane = simulate_serving(TOY, ContinuousBatchingScheduler,
                            toy_poisson(200), slots=4)
    graph = ServingSimulator(TOY, ContinuousBatchingScheduler,
                             toy_poisson(200), slots=4,
                             phase_tasks=4).run()
    assert graph.n_requests == lane.n_requests
    for stat in ("ttft", "tpot", "e2e"):
        a, b = getattr(lane, stat), getattr(graph, stat)
        assert b.p50 == pytest.approx(a.p50, rel=1e-9)
        assert b.p99 == pytest.approx(a.p99, rel=1e-9)
        assert b.mean == pytest.approx(a.mean, rel=1e-9)


def test_graph_mode_records_real_task_structure():
    rep = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(30),
                           replicas=1, slots=2, phase_tasks=2).run()
    names = [r.task.name for r in rep.sim_result.records]
    assert any(n.startswith("prefill/r0/c0") for n in names)
    assert any(n.startswith("decode/r0/c1") for n in names)
    assert any("/kv" in n for n in names)
    resources = {r.task.resource for r in rep.sim_result.records}
    assert resources == {"replica0", "replica0:kv"}
    # KV writes depend on their chunk: they never precede it
    by_tid = {r.task.tid: r for r in rep.sim_result.records}
    for r in rep.sim_result.records:
        for d in r.task.deps:
            assert by_tid[d].end <= r.start + 1e-12


def test_graph_mode_rejects_bad_args():
    with pytest.raises(ValueError):
        ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(5),
                         phase_tasks=-1)
    with pytest.raises(TypeError):          # one engine: no engine= option
        ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(5),
                         engine="dict")


# ---------------------------------------------------------------------------
# speculative decode leap with rollback (PR 4)
# ---------------------------------------------------------------------------


class ScriptedInterveningScheduler(BucketedPrefillScheduler):
    """A custom policy that is decode-stable but *not* steady: it
    interrupts a decode batch to admit whatever arrived, even while slots
    are free — exactly the case the old steady_decode leap had to skip.
    Inherits bucketed admission; declares only the speculative contract."""

    name = "scripted"
    steady_decode = False
    decode_stable = True


class PerStepScripted(ScriptedInterveningScheduler):
    """The scripted policy without the speculative contract: every decode
    step is its own phase."""

    decode_stable = False


def _light_traffic(n=300, seed=4):
    # low rate + long outputs: replicas decode with free slots, so leaps
    # are speculative and arrivals frequently land mid-leap
    return poisson_workload(6.0, n, prompt=LengthDist(mean=64, cv=0.5),
                            output=LengthDist(mean=64, cv=0.6), seed=seed)


def test_speculative_leap_exact_rollback_parity():
    """Scripted mid-leap interventions: metrics must match the per-step
    simulation (record_events=True disables all fusion) to round-off."""
    per_step = simulate_serving(TOY, lambda: ScriptedInterveningScheduler(32),
                                _light_traffic(), slots=8,
                                record_events=True)
    leaped = simulate_serving(TOY, lambda: ScriptedInterveningScheduler(32),
                              _light_traffic(), slots=8)
    assert leaped.n_requests == per_step.n_requests
    assert leaped.output_tokens == per_step.output_tokens
    a, b = _metric_rows(per_step), _metric_rows(leaped)
    for ra, rb in zip(a, b):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)
    for stat in ("ttft", "tpot", "e2e"):
        assert getattr(leaped, stat).p99 == pytest.approx(
            getattr(per_step, stat).p99, rel=1e-9)


def test_speculative_leap_actually_fuses_and_rolls_back():
    """The fast path must engage (fewer decode tasks than steps) and
    truncated leaps must appear in the records."""
    leaped = simulate_serving(TOY, lambda: ScriptedInterveningScheduler(32),
                              _light_traffic(), slots=8)
    per_step = simulate_serving(TOY, lambda: ScriptedInterveningScheduler(32),
                                _light_traffic(), slots=8,
                                record_events=True)
    decode_leaped = [r for r in leaped.sim_result.records
                     if r.task.kind == "decode"]
    decode_steps = [r for r in per_step.sim_result.records
                    if r.task.kind == "decode"]
    assert len(decode_leaped) < 0.7 * len(decode_steps)   # fusion engaged
    fused = [r for r in decode_leaped if "x" in r.task.name.split("/")[-1]]
    assert fused                                          # k>1 leaps exist


def test_speculative_leap_continuous_matches_per_step():
    per_step = simulate_serving(TOY, ContinuousBatchingScheduler,
                                _light_traffic(seed=9), slots=8,
                                record_events=True)
    leaped = simulate_serving(TOY, ContinuousBatchingScheduler,
                              _light_traffic(seed=9), slots=8)
    for ra, rb in zip(_metric_rows(per_step), _metric_rows(leaped)):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)


class _QuadraticCost(ServingCostModel):
    """Overrides the documented decode_step_time hook (non-affine in
    ctx): the leap's inlined affine fast path must not bypass it."""

    def decode_step_time(self, n_active, total_ctx):
        base = ServingCostModel.decode_step_time(self, n_active, total_ctx)
        return base * (1.0 + 1e-5 * max(0, total_ctx))


def test_decode_step_time_override_honored_by_leap():
    cost = _QuadraticCost(name="quad", prefill_fixed=1e-3,
                          prefill_per_token=2e-5, decode_fixed=2e-3,
                          decode_per_token=5e-4, decode_per_ctx_token=1e-7)
    per_step = simulate_serving(cost, ContinuousBatchingScheduler,
                                toy_poisson(150, seed=6), slots=4,
                                record_events=True)
    leaped = simulate_serving(cost, ContinuousBatchingScheduler,
                              toy_poisson(150, seed=6), slots=4)
    for ra, rb in zip(_metric_rows(per_step), _metric_rows(leaped)):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)
    # and the override actually changes the outcome vs the affine model
    affine = simulate_serving(
        ServingCostModel(name="aff", prefill_fixed=1e-3,
                         prefill_per_token=2e-5, decode_fixed=2e-3,
                         decode_per_token=5e-4, decode_per_ctx_token=1e-7),
        ContinuousBatchingScheduler, toy_poisson(150, seed=6), slots=4)
    assert leaped.e2e.p99 > affine.e2e.p99


class _ThresholdAdmitScheduler(ContinuousBatchingScheduler):
    """decode_stable policy whose mid-batch decision depends on queue
    *depth*: it interrupts decoding to admit only when >= 2 requests are
    queued, so a sibling replica popping the queue mid-leap changes its
    next decision (the rollback trigger beyond arrivals)."""

    name = "threshold"
    steady_decode = False
    decode_stable = True

    def decide(self, replica, queue, now):
        if replica.free_slots > 0 and len(queue) >= 2:
            n = min(replica.free_slots, len(queue))
            reqs = [queue.popleft() for _ in range(n)]
            return Prefill(tuple(reqs),
                           sum(r.prompt_tokens for r in reqs))
        if replica.any_decoding:
            return Decode()
        if queue and replica.free_slots > 0:    # drain the tail
            req = queue.popleft()
            return Prefill((req,), req.prompt_tokens)
        return None


def test_sibling_queue_pop_rolls_back_leap_multi_replica():
    """Queue-depth-sensitive decode_stable policy on two replicas:
    leaped metrics must match the per-step ground truth exactly."""
    wl = lambda: poisson_workload(    # noqa: E731
        8.0, 400, prompt=LengthDist(mean=64, cv=0.5),
        output=LengthDist(mean=48, cv=0.6), seed=12)
    per_step = simulate_serving(TOY, _ThresholdAdmitScheduler, wl(),
                                replicas=2, slots=4, record_events=True)
    leaped = simulate_serving(TOY, _ThresholdAdmitScheduler, wl(),
                              replicas=2, slots=4)
    assert leaped.n_requests == per_step.n_requests
    for ra, rb in zip(_metric_rows(per_step), _metric_rows(leaped)):
        assert ra[0] == rb[0]
        for va, vb in zip(ra[1:], rb[1:]):
            assert vb == pytest.approx(va, rel=1e-9, abs=1e-12)


def test_sibling_admission_truncates_armed_leap():
    """White-box: an admission on replica 0 (queue shrinkage) must roll
    replica 1's armed speculative leap back to the next step boundary —
    a decode_stable policy's mid-batch decision may depend on queue
    depth, not just on arrivals."""
    wl = toy_poisson(4)
    sim = ServingSimulator(TOY, ContinuousBatchingScheduler, wl,
                           replicas=2, slots=2)
    lane1 = sim._lanes[1]
    # fabricate an in-flight fused decode (10 steps, 0.1s apart) on r1
    lane1.busy = True
    lane1.starts.append(0.0)
    lane1.ends.append(1.0)
    lane1.kinds.append("decode")
    lane1.infos.append((2, 10))
    lane1._handler = lambda now: None
    lane1.busy_time += 1.0
    bounds = [round(0.1 * i, 10) for i in range(1, 11)]
    sim._leap[1] = (bounds, 2)
    sim._decode_k[1] = 10
    # replica 0 admits a queued request at t=0.25
    req = wl.requests[0]
    sim._start_prefill(sim.replicas[0], Prefill((req,), req.prompt_tokens),
                       now=0.25)
    assert sim._leap[1] is None                  # disarmed
    assert sim._decode_k[1] == 3                 # boundary 0.3 = step 3
    assert lane1.ends[-1] == pytest.approx(0.3)  # fused task truncated
    assert lane1.epoch == 1                      # stale completion voided
    assert lane1.infos[-1] == (2, 3)             # record reflects truth


def test_non_stable_scheduler_never_leaps():
    """A policy that declares neither contract must run per-step even
    when fusing would be possible."""

    class PlainScheduler(ContinuousBatchingScheduler):
        name = "plain"
        steady_decode = False
        decode_stable = False

    rep = simulate_serving(TOY, PlainScheduler, _light_traffic(n=60),
                           slots=4)
    decode_names = [r.task.name for r in rep.sim_result.records
                    if r.task.kind == "decode"]
    assert decode_names
    assert not any("x" in n.split("/")[-1] for n in decode_names)


# ---------------------------------------------------------------------------
# parity: virtual continuous batching vs the real BatchedServer loop
# ---------------------------------------------------------------------------

# (arrival_step, prompt_len, max_new): arrivals join the queue after that
# many real decode steps; the server never goes idle mid-trace.
PARITY_TRACE = [(0, 3, 4), (0, 2, 6), (0, 2, 3), (2, 1, 4), (3, 2, 3),
                (4, 1, 2), (4, 2, 5)]
PARITY_SLOTS = 2


def _run_real_server(trace, slots):
    from repro.launch.serve import BatchedServer, Request

    vocab = 8

    def stub(params, state, tokens, pos):
        return np.zeros((slots, vocab), np.float32), state

    server = BatchedServer(cfg=None, batch_slots=slots, max_len=64,
                           decode_fn=stub, record_events=True)
    server.load(None)
    reqs = [Request(i, np.ones(p, np.int32), m)
            for i, (_, p, m) in enumerate(trace)]
    pending = []
    steps_taken = 0
    guard = 0
    while not all(r.done for r in reqs):
        for i, (s, _, _) in enumerate(trace):
            if s == steps_taken:
                pending.append(reqs[i])
        while pending and server.admit(pending[0]):
            pending.pop(0)
        server.step()
        steps_taken += 1
        guard += 1
        assert guard < 500, "real server failed to drain the trace"
    return server.events


def _run_virtual_server(trace, slots):
    unit = ServingCostModel(name="unit", prefill_fixed=0.0,
                            prefill_per_token=0.0, decode_fixed=1.0,
                            decode_per_token=0.0, decode_per_ctx_token=0.0)
    rows = [(0.0 if s == 0 else s - 0.5, p, m) for s, p, m in trace]
    sim = ServingSimulator(unit, ContinuousBatchingScheduler,
                           trace_workload(rows), replicas=1, slots=slots,
                           record_events=True)
    return sim.run().events


def test_virtual_continuous_matches_real_batched_server():
    real = _run_real_server(PARITY_TRACE, PARITY_SLOTS)
    virtual = _run_virtual_server(PARITY_TRACE, PARITY_SLOTS)
    assert virtual == real


# ---------------------------------------------------------------------------
# capacity planning
# ---------------------------------------------------------------------------


def test_capacity_planner_finds_minimal_replicas():
    slo = SLO(ttft_p99=0.4, tpot_p99=0.02)
    planner = CapacityPlanner(
        TOY, ContinuousBatchingScheduler,
        lambda: toy_poisson(400, rate=60.0, seed=0), slo)
    plan = planner.plan(axis="replicas", cap=16, slots=8)
    assert plan.feasible
    assert slo.satisfied_by(plan.report)
    # every probed value below the answer failed the SLO
    below = [v for v, ok in plan.probes.items() if v < plan.value]
    assert all(not plan.probes[v] for v in below)
    assert plan.value == 1 or below


def test_capacity_planner_reports_infeasible():
    heavy = ServingCostModel(name="slow", decode_fixed=0.5,
                             decode_per_token=0.1)
    plan = CapacityPlanner(
        heavy, ContinuousBatchingScheduler,
        lambda: toy_poisson(50, rate=50.0, seed=1),
        SLO(ttft_p99=0.01)).plan(cap=4)
    assert not plan.feasible
    assert plan.value == 4


def test_capacity_planner_slots_axis():
    slo = SLO(e2e_p99=3.0)
    plan = CapacityPlanner(
        TOY, ContinuousBatchingScheduler,
        lambda: toy_poisson(200, rate=25.0, seed=2), slo).plan(
            axis="slots", cap=32, replicas=1)
    assert plan.feasible
    assert slo.satisfied_by(plan.report)


# ---------------------------------------------------------------------------
# DSE serving axis + trace export
# ---------------------------------------------------------------------------


def test_dse_sweep_serving_axis():
    from repro.core.avsm.model import annotate_system
    from repro.core.dse import DesignSpaceExplorer
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.core.taskgraph.ops import matmul_op

    class FixedBuilder:
        """Stands in for ServingCostModelBuilder (keyed per system)."""

        def model_for(self, system):
            scale = 819e9 / system.chip.memory.bandwidth
            return ServingCostModel(
                name=system.name, decode_fixed=2e-3 * scale,
                decode_per_token=5e-4 * scale, prefill_per_token=2e-5)

    base = SystemDescription(name="chip", chip=tpu_v5e_chip(), torus=())
    systems = {"base": base,
               "fast": annotate_system(base, mem_bandwidth=1638e9)}
    dse = DesignSpaceExplorer({"w": [matmul_op("m", "m", 64, 64, 64)]})
    results = dse.sweep_serving(
        systems,
        traffics={"poisson": lambda: toy_poisson(150, seed=0),
                  "bursty": lambda: bursty_workload(5, 40, 150, seed=0)},
        schedulers={"continuous": ContinuousBatchingScheduler,
                    "static": lambda: StaticBatchScheduler(4, 0.1)},
        cost_builder=FixedBuilder(), replicas=1, slots=4)
    assert len(results) == 2 * 2 * 2
    assert all(r.report.n_requests == 150 for r in results)
    ranked = [r.ttft_p99 for r in results]
    assert ranked == sorted(ranked)


def test_serving_chrome_trace_valid(tmp_path):
    rep = simulate_serving(TOY, ContinuousBatchingScheduler, toy_poisson(40),
                           replicas=2, slots=4)
    p = tmp_path / "serve.trace.json"
    serving_chrome_trace(rep, str(p))
    data = json.loads(p.read_text())
    evs = data["traceEvents"]
    assert any(e.get("pid") == 0 and e.get("ph") == "X" for e in evs)
    assert any(e.get("pid") == 1 and e.get("cat") == "request" for e in evs)
    assert any(e.get("ph") == "C" for e in evs)
    req_spans = [e for e in evs if e.get("cat") == "request"]
    assert len(req_spans) == rep.n_requests
    # queue-depth counter never dips negative (arrival/admit tie-break)
    depths = [e["args"]["requests"] for e in evs if e.get("ph") == "C"]
    assert min(depths) >= 0
    # exactly one metadata row per (replica, slot) lane
    lane_meta = [e for e in evs
                 if e.get("pid") == 1 and e.get("ph") == "M"
                 and e.get("name") == "thread_name"]
    assert len(lane_meta) == len({(e["tid"]) for e in lane_meta})
