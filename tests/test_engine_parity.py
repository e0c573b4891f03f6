"""Golden parity for the optimized simulation core.

The rewritten engines — virtual-time processor sharing, the array-backed
static and dynamic engines, the vectorized what-if sweep, and the
parallel serving sweep — must reproduce the seed engine's results:

  * ``simulate_static`` (static graphs) and ``DynamicSimulator``
    (everything that injects) vs the frozen seed engine
    (``tests/reference_engine``) on real compiled graphs, randomized
    DAGs, mid-flight traffic injection and graph templates;
  * ``simulate_static`` vs ``DynamicSimulator`` bit for bit on randomized
    DAGs, including the picosecond durations where the seed engine is
    documented to be wrong;
  * ``what_if_sweep`` batched estimates vs the per-value estimate loop
    for every backend;
  * parallel ``sweep_serving`` vs its serial run, bit-identical.

Plus the regression test for the shared-channel completion tolerance:
near-ties are now grouped by a *relative* epsilon scaled by each task's
full-rate duration, not the seed's absolute 1e-15 seconds.
"""
import random

import numpy as np
import pytest
import reference_engine
from _hypothesis_compat import given, settings, st

from repro.core.config import LM_SHAPES, get_arch
from repro.core.dse import DesignSpaceExplorer
from repro.core.estimator import get_backend
from repro.core.hw import tpu_v5e_pod, virtex7_nce_system
from repro.core.sim.engine import (DynamicSimulator, GraphTemplate,
                                   ResourceSpec, StaticCache, Task,
                                   simulate_static)
from repro.core.taskgraph.builders import ShardPlan, convnet_ops, lm_step_ops
from repro.core.taskgraph.compiler import compile_ops

REL = 1e-9


def _spans(result):
    return {r.task.tid: (r.start, r.end) for r in result.records}


def _assert_same_result(ref, other, rel=REL):
    """makespan, per-record start/end, resource_busy, and layer times."""
    assert other.makespan == pytest.approx(ref.makespan, rel=rel)
    sa, sb = _spans(ref), _spans(other)
    assert set(sa) == set(sb)
    for tid, (s, e) in sa.items():
        assert sb[tid][0] == pytest.approx(s, rel=rel, abs=1e-15), tid
        assert sb[tid][1] == pytest.approx(e, rel=rel, abs=1e-15), tid
    assert set(ref.resource_busy) == set(other.resource_busy)
    for res, busy in ref.resource_busy.items():
        assert other.resource_busy[res] == pytest.approx(busy, rel=rel)
    assert set(ref.layer_time) == set(other.layer_time)
    for lay, (s, e) in ref.layer_time.items():
        assert other.layer_time[lay][0] == pytest.approx(s, rel=rel,
                                                         abs=1e-15)
        assert other.layer_time[lay][1] == pytest.approx(e, rel=rel,
                                                         abs=1e-15)


@pytest.fixture(scope="module")
def compiled_graphs():
    vgg = compile_ops(convnet_ops(get_arch("dilated-vgg").model),
                      virtex7_nce_system())
    spec = get_arch("qwen1.5-0.5b")
    lm = compile_ops(lm_step_ops(spec.model, LM_SHAPES["train_4k"],
                                 ShardPlan()), tpu_v5e_pod())
    return {"vgg": vgg, "lm": lm}


# ---------------------------------------------------------------------------
# golden parity on real compiled graphs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["vgg", "lm"])
def test_simulator_matches_seed_engine_on_compiled_graph(compiled_graphs,
                                                         name):
    g = compiled_graphs[name]
    ref = reference_engine.Simulator(
        g.tasks, resources=g.resources, durations=g.durations).run()
    new = DynamicSimulator(g.tasks, resources=g.resources,
                           durations=g.durations).run()
    _assert_same_result(ref, new)


@pytest.mark.parametrize("name", ["vgg", "lm"])
def test_static_fast_path_matches_seed_engine_on_compiled_graph(
        compiled_graphs, name):
    g = compiled_graphs[name]
    ref = reference_engine.Simulator(
        g.tasks, resources=g.resources, durations=g.durations).run()
    fast = simulate_static(g.tasks, g.resources, g.durations,
                           cache=g.sim_cache())
    _assert_same_result(ref, fast)


def test_static_fast_path_cache_reuse_across_reannotation(compiled_graphs):
    from repro.core.avsm.model import AVSM

    g = compiled_graphs["lm"]
    avsm = AVSM(system=g.system, graph=g)
    variant = avsm.what_if(mem_bandwidth=1.6e12).graph
    assert variant.sim_cache() is g.sim_cache()    # shared structure
    ref = reference_engine.Simulator(
        variant.tasks, resources=variant.resources,
        durations=variant.durations).run()
    fast = simulate_static(variant.tasks, variant.resources,
                           variant.durations, cache=variant.sim_cache())
    _assert_same_result(ref, fast)


# ---------------------------------------------------------------------------
# golden parity on randomized DAGs (mixed fifo/shared resources)
# ---------------------------------------------------------------------------


#: durations the seed engine gets right: its absolute 1e-15 s channel
#: tolerance finishes near-ties early when durations are themselves tiny
#: (pinned by test_near_tie_on_shared_channel_not_completed_early)
SEED_DURATIONS = st.one_of(st.just(0.0), st.floats(1e-9, 2.0))
#: the full range, subnormals included
ALL_DURATIONS = st.floats(0.0, 2.0)


def _random_tasks(data, n, durations):
    n_res = data.draw(st.integers(1, 4))
    specs = {}
    for r in range(n_res):
        mode = data.draw(st.sampled_from(["fifo", "shared"]))
        servers = data.draw(st.integers(1, 3))
        specs[f"r{r}"] = ResourceSpec(f"r{r}", servers=servers, mode=mode)
    tasks = []
    for i in range(n):
        deps = tuple(data.draw(st.sets(st.integers(0, i - 1), max_size=3))) \
            if i else ()
        dur = data.draw(durations)
        tasks.append(Task(i, f"t{i}", f"L{i % 5}", f"r{i % n_res}", dur,
                          deps=deps))
    return tasks, specs


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_random_dag_parity_all_engines(data):
    n = data.draw(st.integers(2, 50))
    tasks, specs = _random_tasks(data, n, SEED_DURATIONS)
    ref = reference_engine.Simulator(tasks, resources=specs).run()
    dyn = DynamicSimulator(tasks, resources=specs).run()
    fast = simulate_static(tasks, specs)
    _assert_same_result(ref, dyn)
    _assert_same_result(ref, fast)


def test_static_fast_path_ties_break_by_tid_not_list_order():
    """Equal-time FIFO ready ties must schedule in tid order (the general
    engine's rule), even when the task list is not tid-sorted."""
    tasks = [Task(1, "busy", "L", "r", 5.0),
             Task(9, "w1", "L", "r", 1.0),
             Task(3, "w2", "L", "r", 2.0)]
    ref = reference_engine.Simulator(tasks).run()
    fast = simulate_static(tasks)
    _assert_same_result(ref, fast)
    spans = _spans(fast)
    assert spans[3][0] == pytest.approx(5.0)     # lower tid runs first
    assert spans[9][0] == pytest.approx(7.0)
    # same rule on a shared channel with identical virtual finishes
    shared = [Task(7, "a", "L", "link", 1.0), Task(2, "b", "L", "link", 1.0)]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    ref = reference_engine.Simulator(shared, resources=specs).run()
    _assert_same_result(ref, simulate_static(shared, specs))


def test_static_cache_is_reusable_across_duration_vectors():
    tasks = [Task(i, f"t{i}", "L", "link" if i % 2 else "nce",
                  0.1 + 0.01 * i, deps=(i - 1,) if i % 3 == 0 and i else ())
             for i in range(40)]
    specs = {"link": ResourceSpec("link", servers=2, mode="shared")}
    cache = StaticCache(tasks)
    for scale in (1.0, 0.5, 2.0):
        durs = [t.duration * scale for t in tasks]
        ref = reference_engine.Simulator(tasks, resources=specs,
                                         durations=durs).run()
        fast = simulate_static(tasks, specs, durs, cache=cache)
        _assert_same_result(ref, fast)


# ---------------------------------------------------------------------------
# dynamic engine: DynamicSimulator vs the seed engine and simulate_static
# ---------------------------------------------------------------------------


def _assert_identical_result(ref, fast):
    """Bit-exact parity: both array engines perform the same arithmetic
    in the same order."""
    assert fast.makespan == ref.makespan
    assert _spans(fast) == _spans(ref)
    assert fast.resource_busy == ref.resource_busy
    assert fast.layer_time == ref.layer_time


@pytest.mark.parametrize("name", ["vgg", "lm"])
def test_dynamic_engine_from_static_cache_matches_seed_engine_on_compiled_graph(
        compiled_graphs, name):
    """The ``DynamicCache.from_static`` path: the dynamic engine seeded
    from ``CompiledGraph.sim_cache()`` against the seed engine."""
    g = compiled_graphs[name]
    ref = reference_engine.Simulator(
        g.tasks, resources=g.resources, durations=g.durations).run()
    fast = DynamicSimulator(g.tasks, resources=g.resources,
                            durations=g.durations, cache=g.sim_cache()).run()
    _assert_same_result(ref, fast)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_random_dag_parity_dynamic_engine(data):
    """Over the full duration range, subnormals included, the two engines
    that are both meant to be right there agree bit for bit."""
    n = data.draw(st.integers(2, 50))
    tasks, specs = _random_tasks(data, n, ALL_DURATIONS)
    static = simulate_static(tasks, specs)
    dyn = DynamicSimulator(tasks, resources=specs).run()
    _assert_identical_result(static, dyn)


def _traffic_script(seed=11, n_arrivals=40):
    """A seeded mid-flight injection scenario: a static prefix plus timed
    arrivals that inject chains depending on completed *and* in-flight
    tasks, driven identically on either engine."""
    rng = random.Random(seed)
    static = [Task(i, f"s{i}", f"L{i % 3}", f"r{i % 3}", rng.uniform(0.1, 2),
                   deps=(i - 1,) if i and rng.random() < 0.5 else ())
              for i in range(10)]
    specs = {"r0": ResourceSpec("r0", servers=2),
             "r1": ResourceSpec("r1", servers=1),
             "r2": ResourceSpec("r2", servers=2, mode="shared")}
    arrivals = []
    tid = 10
    for _ in range(n_arrivals):
        t = rng.uniform(0.0, 20.0)
        chain = []
        prev = rng.randrange(tid) if rng.random() < 0.5 else None
        for _ in range(rng.randint(1, 3)):
            chain.append((tid, rng.choice(["r0", "r1", "r2"]),
                          rng.uniform(0.05, 1.0),
                          (prev,) if prev is not None else ()))
            prev = tid
            tid += 1
        arrivals.append((t, chain))
    return static, specs, arrivals


def _run_traffic(sim_cls):
    """Drive ``sim_cls`` (the seed engine or DynamicSimulator, which share
    the dynamic API) through the seeded scenario."""
    static, specs, arrivals = _traffic_script()
    completed = []
    sim = sim_cls(static, resources=specs,
                  on_complete=lambda t, now: completed.append((t.tid, now)))

    def make_inject(chain):
        def fire():
            for tid, res, dur, deps in chain:
                # deps may reference completed or in-flight tasks
                deps = tuple(d for d in deps if d in sim_injected)
                sim.inject(Task(tid, f"d{tid}", "dyn", res, dur, deps=deps))
                sim_injected.add(tid)
        return fire

    sim_injected = set(range(len(static)))
    for t, chain in arrivals:
        sim.at(t, make_inject(chain))
    return sim.run(), completed


def test_dynamic_engine_traffic_injection_parity():
    """Task-for-task golden parity with the seed engine on a seeded
    traffic scenario with mid-flight injection: spans, completion order,
    aggregates."""
    ref, ref_completed = _run_traffic(reference_engine.Simulator)
    fast, fast_completed = _run_traffic(DynamicSimulator)
    _assert_same_result(ref, fast)
    # same causal order; times to the shared channel's round-off
    assert [tid for tid, _ in fast_completed] == \
        [tid for tid, _ in ref_completed]
    for (_, t_fast), (_, t_ref) in zip(fast_completed, ref_completed):
        assert t_fast == pytest.approx(t_ref, rel=REL)


def test_dynamic_engine_template_matches_individual_injection():
    """A GraphTemplate instance must behave exactly like injecting its
    tasks one by one on the seed engine."""
    tpl_tasks = [Task(0, "c0", "lay", "rep", 1.0),
                 Task(1, "kv0", "kv", "rep:kv", 0.0, deps=(0,)),
                 Task(2, "c1", "lay", "rep", 1.0, deps=(0,)),
                 Task(3, "kv1", "kv", "rep:kv", 0.0, deps=(2,))]
    tpl = GraphTemplate(tpl_tasks, tail=2)
    fired = []
    fast = DynamicSimulator()
    for k, t0 in enumerate((0.5, 1.25, 4.0)):
        fast.at(t0, lambda k=k: fast.inject_template(
            tpl, [0.4, 0.0, 0.3, 0.0],
            on_done=lambda now, k=k: fired.append((k, now))))
    res_fast = fast.run()

    ref = reference_engine.Simulator()
    ref_fired = []
    durs = [0.4, 0.0, 0.3, 0.0]

    def inject_all(base):
        for t, d in zip(tpl_tasks, durs):
            ref.inject(Task(base + t.tid, t.name, t.layer, t.resource, d,
                            deps=tuple(base + x for x in t.deps),
                            kind=t.kind))
    for k, t0 in enumerate((0.5, 1.25, 4.0)):
        ref.at(t0, lambda k=k: inject_all(4 * k))
    ref.on_complete = lambda t, now: (
        ref_fired.append((t.tid // 4, now)) if t.tid % 4 == 2 else None)
    res_ref = ref.run()
    assert res_fast.makespan == res_ref.makespan
    assert fired == ref_fired
    assert _spans(res_fast) == _spans(res_ref)
    assert res_fast.resource_busy == res_ref.resource_busy


def test_template_lane_generic_template_matches_seed_injection():
    """A TemplateLane phase with a *non-chain* template (diamond deps +
    sidecar) must replay exactly what the seed engine computes for the
    same tasks — the lane's deferred-schedule path vs live events.
    Spans compare by name: lanes materialize per-lane task ids."""
    tpl_tasks = [Task(0, "a", "rep", "rep", 0.0),
                 Task(1, "b", "rep:kv", "rep:kv", 0.0, deps=(0,)),
                 Task(2, "c", "rep", "rep", 0.0, deps=(0,)),
                 Task(3, "d", "rep", "rep", 0.0, deps=(1, 2))]
    tpl = GraphTemplate(tpl_tasks, tail=3)
    durs = [1.0, 0.5, 0.7, 0.3]
    # tail end, precomputed: a 0->1, b(kv) 1->1.5, c 1->1.7,
    # d ready max(1.5, 1.7) -> 1.7->2.0
    fired = []
    fast = DynamicSimulator()
    lane = fast.template_lane("rep")
    for k, (t0, end) in enumerate(((0.5, 2.5), (4.0, 6.0))):
        fast.at(t0, lambda k=k, end=end: lane.submit(
            tpl, durs, end, lambda now, k=k: fired.append((k, now))))
    res_fast = fast.run()

    ref = reference_engine.Simulator()
    ref_fired = []

    def inject_all(base):
        for t, d in zip(tpl_tasks, durs):
            ref.inject(Task(base + t.tid, t.name, t.layer, t.resource, d,
                            deps=tuple(base + x for x in t.deps),
                            kind=t.kind))
    for k, t0 in enumerate((0.5, 4.0)):
        ref.at(t0, lambda k=k: inject_all(4 * k))
    ref.on_complete = lambda t, now: (
        ref_fired.append((t.tid // 4, now)) if t.tid % 4 == 3 else None)
    res_ref = ref.run()
    assert res_fast.makespan == res_ref.makespan
    assert fired == ref_fired
    by_name_fast = sorted((r.task.name, r.start, r.end)
                          for r in res_fast.records)
    by_name_ref = sorted((r.task.name, r.start, r.end)
                         for r in res_ref.records)
    assert by_name_fast == by_name_ref
    assert res_fast.resource_busy == res_ref.resource_busy
    assert res_fast.layer_time == res_ref.layer_time


def test_template_lane_rejects_bad_usage():
    sim = DynamicSimulator()
    lane = sim.template_lane("rep")
    bad = GraphTemplate([Task(0, "a", "rep", "rep", 0.0),
                         Task(1, "b", "rep", "rep", 0.0)])
    # forward dep: task 0 depending on a later id is rejected up front
    fwd = GraphTemplate([Task(0, "a", "rep", "rep", 0.0, deps=(1,)),
                         Task(1, "b", "rep", "rep", 0.0)])
    with pytest.raises(ValueError):
        lane.submit(fwd, [1.0, 1.0], 2.0, lambda now: None)
    lane2 = sim.template_lane("rep2")
    lane2.submit(bad, [1.0, 1.0], 1.0, lambda now: None)
    with pytest.raises(RuntimeError):       # busy lane refuses a submit
        lane2.submit(bad, [1.0, 1.0], 2.0, lambda now: None)
    with pytest.raises(RuntimeError):       # non-burst entries can't roll back
        lane2.truncate(0.5)


def test_dynamic_engine_rejects_duplicate_and_unknown():
    sim = DynamicSimulator([Task(0, "a", "L", "r", 1.0)])
    with pytest.raises(ValueError):
        sim.inject(Task(0, "dup", "L", "r", 1.0))
    with pytest.raises(ValueError):
        sim.inject(Task(5, "b", "L", "r", 1.0, deps=(99,)))
    with pytest.raises(ValueError):
        sim.at(-1.0, lambda: None)


def test_dynamic_cache_seeded_from_static_cache(compiled_graphs):
    """Seeding from CompiledGraph.sim_cache() reuses the CSR and yields
    the same result as building from the task list."""
    g = compiled_graphs["vgg"]
    seeded = DynamicSimulator(g.tasks, resources=g.resources,
                              durations=g.durations,
                              cache=g.sim_cache()).run()
    scratch = DynamicSimulator(g.tasks, resources=g.resources,
                               durations=g.durations).run()
    _assert_identical_result(scratch, seeded)


# ---------------------------------------------------------------------------
# shared-channel completion tolerance (satellite regression)
# ---------------------------------------------------------------------------


def test_near_tie_on_shared_channel_not_completed_early():
    """Two near-equal tasks at picosecond scale: the seed's absolute 1e-15
    cutoff finished task b with half its work left; the relative epsilon
    keeps it running until its true completion (processor sharing: a ends
    at 2e-15, b then runs at full rate and ends at 3e-15)."""
    tasks = [Task(0, "a", "L", "link", 1e-15),
             Task(1, "b", "L", "link", 2e-15)]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert spans[0][1] == pytest.approx(2e-15, rel=1e-9)
    assert spans[1][1] == pytest.approx(3e-15, rel=1e-9)
    # the seed engine exhibits the defect: both complete at 2e-15
    seed = reference_engine.Simulator(tasks, resources=specs).run()
    seed_spans = _spans(seed)
    assert seed_spans[1][1] == pytest.approx(2e-15, rel=1e-9)
    # the fast path applies the same relative epsilon
    fast = simulate_static(tasks, specs)
    assert _spans(fast)[1][1] == pytest.approx(3e-15, rel=1e-9)


def test_true_ties_still_complete_together():
    tasks = [Task(0, "a", "L", "link", 1.0), Task(1, "b", "L", "link", 1.0)]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    for run in (DynamicSimulator(tasks, resources=specs).run(),
                simulate_static(tasks, specs)):
        spans = _spans(run)
        assert spans[0] == pytest.approx((0.0, 2.0))
        assert spans[1] == pytest.approx((0.0, 2.0))


# ---------------------------------------------------------------------------
# vectorized what-if sweep vs the per-value loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["roofline", "analytic", "des"])
def test_what_if_sweep_vectorized_matches_loop(compiled_graphs, backend):
    from repro.core.avsm.model import AVSM

    base = tpu_v5e_pod()
    spec = get_arch("qwen1.5-0.5b")
    ops = lm_step_ops(spec.model, LM_SHAPES["train_4k"], ShardPlan())
    dse = DesignSpaceExplorer({"lm": ops})
    values = list(np.linspace(50e9, 200e9, 5))
    swept = dse.what_if_sweep("lm", base, "link_bandwidth", values,
                              backend=backend)
    est = get_backend(backend)
    avsm = AVSM(system=base, graph=dse.compiled("lm", base))
    for v, rep in swept:
        ref = est.estimate(avsm.what_if(link_bandwidth=v).graph)
        assert rep.step_time == pytest.approx(ref.step_time, rel=REL)
        assert rep.t_compute == pytest.approx(ref.t_compute, rel=REL)
        assert rep.t_memory == pytest.approx(ref.t_memory, rel=REL)
        assert rep.t_collective == pytest.approx(ref.t_collective,
                                                 rel=REL, abs=1e-18)
        ref_layers = {l.name: l for l in ref.layers}
        for lay in rep.layers:
            assert lay.time == pytest.approx(ref_layers[lay.name].time,
                                             rel=REL, abs=1e-18)
            assert lay.bound == ref_layers[lay.name].bound


def test_estimate_many_falls_back_on_unrelated_graphs(compiled_graphs):
    est = get_backend("analytic")
    graphs = [compiled_graphs["vgg"], compiled_graphs["lm"]]
    reps = est.estimate_many(graphs)
    for g, rep in zip(graphs, reps):
        ref = est.estimate(g)
        assert rep.step_time == pytest.approx(ref.step_time, rel=REL)


# ---------------------------------------------------------------------------
# parallel sweeps are bit-identical to serial
# ---------------------------------------------------------------------------


def _toy_serving_axes():
    from repro.core.avsm.model import annotate_system
    from repro.core.hw import SystemDescription, tpu_v5e_chip
    from repro.serve_sim import (ContinuousBatchingScheduler, LengthDist,
                                 ServingCostModel, StaticBatchScheduler,
                                 poisson_workload)

    class FixedBuilder:
        def model_for(self, system):
            scale = 819e9 / system.chip.memory.bandwidth
            return ServingCostModel(
                name=system.name, decode_fixed=2e-3 * scale,
                decode_per_token=5e-4 * scale, prefill_per_token=2e-5)

    base = SystemDescription(name="chip", chip=tpu_v5e_chip(), torus=())
    systems = {"base": base,
               "fast": annotate_system(base, mem_bandwidth=1638e9)}
    traffics = {
        "poisson": lambda: poisson_workload(
            20.0, 120, prompt=LengthDist(mean=128, cv=0.5),
            output=LengthDist(mean=32, cv=0.5), seed=0)}
    schedulers = {"continuous": ContinuousBatchingScheduler,
                  "static": lambda: StaticBatchScheduler(4, 0.1)}
    return systems, traffics, schedulers, FixedBuilder()


def test_parallel_sweep_serving_bit_identical_to_serial():
    from repro.core.taskgraph.ops import matmul_op

    systems, traffics, schedulers, builder = _toy_serving_axes()
    dse = DesignSpaceExplorer({"w": [matmul_op("m", "m", 64, 64, 64)]})
    serial = dse.sweep_serving(systems, traffics, schedulers, builder,
                               replicas=1, slots=4)
    parallel = dse.sweep_serving(systems, traffics, schedulers, builder,
                                 replicas=1, slots=4, workers=2)
    assert len(serial) == len(parallel) == 4
    for a, b in zip(serial, parallel):
        assert (a.system, a.traffic, a.scheduler) == \
            (b.system, b.traffic, b.scheduler)
        ra, rb = a.report, b.report
        assert ra.n_requests == rb.n_requests
        assert ra.duration == rb.duration               # bit-identical
        assert ra.output_tokens == rb.output_tokens
        for stat in ("ttft", "tpot", "e2e", "queue_delay"):
            assert getattr(ra, stat) == getattr(rb, stat)
        assert [(m.rid, m.t_admit, m.t_first, m.t_done)
                for m in ra.requests] == \
            [(m.rid, m.t_admit, m.t_first, m.t_done) for m in rb.requests]
        assert rb.sim_result is None                    # traces stay local


def test_parallel_explore_matches_serial(compiled_graphs):
    from repro.core.avsm.model import annotate_system

    base = virtex7_nce_system()
    systems = {"base": base,
               "2x_bw": annotate_system(base, mem_bandwidth=2 * base.chip.
                                        memory.bandwidth)}
    cfg = get_arch("dilated-vgg").model
    serial = DesignSpaceExplorer({"vgg": convnet_ops(cfg)}).explore(
        systems, keep=2)
    parallel = DesignSpaceExplorer({"vgg": convnet_ops(cfg)}).explore(
        systems, keep=2, workers=2)
    assert [(r.system, r.confirmed.step_time) for r in serial] == \
        [(r.system, r.confirmed.step_time) for r in parallel]
