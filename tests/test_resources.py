"""Resource-model invariants of the DES engine: multi-server FIFO
stations, bandwidth-shared channels (processor sharing), determinism,
and throughput conservation."""
import pytest

from repro.core.hw import tpu_v5e_pod
from repro.core.sim.engine import DynamicSimulator, ResourceSpec, Task


def _spans(res):
    return {r.task.tid: (r.start, r.end) for r in res.records}


# ---------------------------------------------------------------------------
# multi-server FIFO
# ---------------------------------------------------------------------------


def test_multi_server_fifo_parallelism():
    """k servers run k tasks concurrently; n tasks take ceil(n/k) waves."""
    tasks = [Task(i, f"t{i}", "L", "dma", 1.0) for i in range(6)]
    specs = {"dma": ResourceSpec("dma", servers=3, mode="fifo")}
    res = DynamicSimulator(tasks, resources=specs).run()
    assert res.makespan == pytest.approx(2.0)
    assert res.resource_busy["dma"] == pytest.approx(6.0)


def test_single_server_fifo_matches_legacy_exclusive():
    """Default spec (unknown resource) = 1-server FIFO = old behaviour."""
    tasks = [Task(0, "a", "L", "r", 1.0), Task(1, "b", "L", "r", 1.0)]
    res = DynamicSimulator(tasks).run()
    assert res.makespan == pytest.approx(2.0)


def test_fifo_more_servers_than_tasks():
    tasks = [Task(i, f"t{i}", "L", "r", 2.0) for i in range(3)]
    specs = {"r": ResourceSpec("r", servers=8)}
    res = DynamicSimulator(tasks, resources=specs).run()
    assert res.makespan == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# bandwidth-shared channels (processor sharing)
# ---------------------------------------------------------------------------


def test_shared_channel_splits_bandwidth():
    """Two transfers sharing one channel each run at half rate and finish
    together — not strictly serialized (old behaviour: 1.0 then 2.0)."""
    tasks = [Task(0, "a", "L", "link", 1.0), Task(1, "b", "L", "link", 1.0)]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert spans[0] == pytest.approx((0.0, 2.0))
    assert spans[1] == pytest.approx((0.0, 2.0))
    assert res.makespan == pytest.approx(2.0)


def test_shared_channel_total_throughput_conserved():
    """Total work through a width-k channel never exceeds k * full rate:
    makespan >= sum(durations) / k, and equals it under saturation."""
    durs = [0.5, 1.0, 1.5, 2.0, 2.5, 3.5]
    for k in (1, 2, 3):
        tasks = [Task(i, f"t{i}", "L", "link", d) for i, d in enumerate(durs)]
        specs = {"link": ResourceSpec("link", servers=k, mode="shared")}
        res = DynamicSimulator(tasks, resources=specs).run()
        assert res.makespan >= sum(durs) / k - 1e-9
        assert res.resource_busy["link"] == pytest.approx(sum(durs))
    # width 1, all admitted at t=0: channel saturated until the end
    tasks = [Task(i, f"t{i}", "L", "link", d) for i, d in enumerate(durs)]
    res = DynamicSimulator(tasks, resources={
        "link": ResourceSpec("link", servers=1, mode="shared")}).run()
    assert res.makespan == pytest.approx(sum(durs))


def test_shared_channel_under_capacity_runs_full_rate():
    tasks = [Task(0, "a", "L", "link", 2.0), Task(1, "b", "L", "link", 3.0)]
    specs = {"link": ResourceSpec("link", servers=2, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert spans[0] == pytest.approx((0.0, 2.0))
    assert spans[1] == pytest.approx((0.0, 3.0))


def test_shared_channel_late_arrival_processor_sharing():
    """B (work 1) arrives at t=1 while A (work 2) is in flight: both share
    the channel at rate 1/2 from t=1, so both complete at t=3."""
    tasks = [
        Task(0, "a", "L", "link", 2.0),
        Task(1, "gate", "L", "host", 1.0),
        Task(2, "b", "L", "link", 1.0, deps=(1,)),
    ]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert spans[0] == pytest.approx((0.0, 3.0))
    assert spans[2] == pytest.approx((1.0, 3.0))


def test_shared_channel_dependency_causality():
    """A dependent task cannot start before a shared-channel producer
    finishes, even under contention."""
    tasks = [
        Task(0, "x0", "L", "link", 1.0),
        Task(1, "x1", "L", "link", 1.0),
        Task(2, "c", "L", "nce", 0.5, deps=(0,)),
    ]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert spans[2][0] >= spans[0][1] - 1e-9


def test_zero_duration_task_on_shared_channel():
    tasks = [Task(0, "z", "L", "link", 0.0), Task(1, "a", "L", "link", 1.0)]
    specs = {"link": ResourceSpec("link", servers=1, mode="shared")}
    res = DynamicSimulator(tasks, resources=specs).run()
    assert res.makespan == pytest.approx(1.0)
    assert len(res.records) == 2


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------


def _mixed_workload():
    tasks = []
    tid = 0
    for wave in range(5):
        for j in range(7):
            deps = (tid - 7,) if tid >= 7 else ()
            res = ["nce", "dma", "ici_model"][j % 3]
            tasks.append(Task(tid, f"w{wave}j{j}", f"L{wave}", res,
                              0.1 + 0.013 * ((tid * 7919) % 11), deps=deps))
            tid += 1
    specs = {
        "dma": ResourceSpec("dma", servers=2, mode="shared"),
        "ici_model": ResourceSpec("ici_model", servers=2, mode="shared"),
        "nce": ResourceSpec("nce", servers=1, mode="fifo"),
    }
    return tasks, specs


def test_des_deterministic_under_multi_server_resources():
    tasks, specs = _mixed_workload()
    runs = [DynamicSimulator(tasks, resources=specs).run() for _ in range(3)]
    base = runs[0]
    for other in runs[1:]:
        assert other.makespan == base.makespan
        assert [(r.task.tid, r.start, r.end) for r in other.records] == \
            [(r.task.tid, r.start, r.end) for r in base.records]


def test_mixed_workload_invariants():
    tasks, specs = _mixed_workload()
    res = DynamicSimulator(tasks, resources=specs).run()
    spans = _spans(res)
    assert len(spans) == len(tasks)
    for t in tasks:
        for d in t.deps:
            assert spans[t.tid][0] >= spans[d][1] - 1e-9
    # work conservation per resource
    for rname, busy in res.resource_busy.items():
        expect = sum(t.duration for t in tasks if t.resource == rname)
        assert busy == pytest.approx(expect)
    # fifo exclusivity still holds on nce
    nce = sorted(spans[t.tid] for t in tasks if t.resource == "nce")
    for (s1, e1), (s2, e2) in zip(nce, nce[1:]):
        assert s2 >= e1 - 1e-9


def test_duration_override_array():
    """The what-if fast path swaps durations without touching Tasks."""
    tasks = [Task(0, "a", "L", "r", 1.0), Task(1, "b", "L", "r", 1.0,
                                               deps=(0,))]
    res = DynamicSimulator(tasks, durations=[0.5, 0.25]).run()
    assert res.makespan == pytest.approx(0.75)
    assert tasks[0].duration == 1.0          # untouched
    with pytest.raises(ValueError):
        DynamicSimulator(tasks, durations=[0.5])


# ---------------------------------------------------------------------------
# compiled graphs carry the topology-derived resource model
# ---------------------------------------------------------------------------


def test_compiled_graph_resource_specs():
    from repro.core.taskgraph.compiler import compile_ops
    from repro.core.taskgraph.ops import matmul_op

    sys = tpu_v5e_pod()
    g = compile_ops([matmul_op("m", "L", 4096, 4096, 4096)], sys)
    assert g.resources["dma"].servers == sys.chip.memory.num_dma_engines
    assert g.resources["dma"].mode == "shared"
    # 2-D torus with 4 links => 2 links per mesh axis
    assert g.resources["ici_model"].servers == 2
    assert g.resources["ici_model"].mode == "shared"
    assert g.resources["nce"].mode == "fifo"


def test_invalid_resource_spec_rejected():
    with pytest.raises(ValueError):
        ResourceSpec("r", servers=0)
    with pytest.raises(ValueError):
        ResourceSpec("r", mode="psq")
