"""DES engine invariants: causality, resource exclusivity, conservation —
including hypothesis tests over random DAGs."""
import json

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.sim.engine import DynamicSimulator, Task
from repro.core.sim.trace import ascii_gantt, chrome_trace


def test_serial_chain():
    tasks = [Task(i, f"t{i}", "L", "r", 1.0, deps=(i - 1,) if i else ())
             for i in range(5)]
    res = DynamicSimulator(tasks).run()
    assert res.makespan == pytest.approx(5.0)
    assert res.utilization("r") == pytest.approx(1.0)


def test_parallel_resources():
    tasks = [Task(0, "a", "L", "r1", 2.0), Task(1, "b", "L", "r2", 3.0)]
    res = DynamicSimulator(tasks).run()
    assert res.makespan == pytest.approx(3.0)


def test_dependency_blocks_across_resources():
    tasks = [Task(0, "dma", "L", "dma0", 2.0),
             Task(1, "compute", "L", "nce", 1.0, deps=(0,))]
    res = DynamicSimulator(tasks).run()
    recs = {r.task.name: r for r in res.records}
    assert recs["compute"].start == pytest.approx(2.0)


def test_fifo_contention():
    tasks = [Task(0, "a", "L", "r", 1.0), Task(1, "b", "L", "r", 1.0)]
    res = DynamicSimulator(tasks).run()
    assert res.makespan == pytest.approx(2.0)
    spans = sorted((r.start, r.end) for r in res.records)
    assert spans[0][1] <= spans[1][0] + 1e-12     # no overlap on a resource


def test_cycle_detection():
    tasks = [Task(0, "a", "L", "r", 1.0, deps=(1,)),
             Task(1, "b", "L", "r", 1.0, deps=(0,))]
    with pytest.raises(RuntimeError, match="deadlock|cycle"):
        DynamicSimulator(tasks).run()


def test_unknown_dep_rejected():
    with pytest.raises(ValueError):
        DynamicSimulator([Task(0, "a", "L", "r", 1.0, deps=(7,))])


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_random_dag_invariants(data):
    n = data.draw(st.integers(2, 40))
    n_res = data.draw(st.integers(1, 4))
    tasks = []
    for i in range(n):
        deps = tuple(data.draw(st.sets(st.integers(0, i - 1), max_size=3))) \
            if i else ()
        dur = data.draw(st.floats(0.01, 2.0))
        tasks.append(Task(i, f"t{i}", f"L{i % 5}", f"r{i % n_res}", dur,
                          deps=deps))
    res = DynamicSimulator(tasks).run()
    recs = {r.task.tid: r for r in res.records}
    assert len(recs) == n
    # causality: every task starts after all deps end
    for t in tasks:
        for d in t.deps:
            assert recs[t.tid].start >= recs[d].end - 1e-9
    # exclusivity: no overlap within a resource
    by_res = {}
    for r in res.records:
        by_res.setdefault(r.task.resource, []).append((r.start, r.end))
    for spans in by_res.values():
        spans.sort()
        for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
            assert s2 >= e1 - 1e-9
    # conservation: makespan within [max single chain, sum of durations]
    assert res.makespan <= sum(t.duration for t in tasks) + 1e-9
    assert res.makespan >= max(t.duration for t in tasks) - 1e-9
    # busy time per resource == sum of its durations
    for rname, busy in res.resource_busy.items():
        expect = sum(t.duration for t in tasks if t.resource == rname)
        assert busy == pytest.approx(expect)


def test_chrome_trace_valid_json(tmp_path):
    tasks = [Task(0, "a", "L", "nce", 1.0),
             Task(1, "b", "L", "dma0", 0.5, deps=(0,), kind="dma")]
    res = DynamicSimulator(tasks).run()
    p = tmp_path / "trace.json"
    chrome_trace(res, str(p))
    data = json.loads(p.read_text())
    assert any(ev.get("ph") == "X" for ev in data["traceEvents"])
    g = ascii_gantt(res)
    assert "nce" in g and "dma0" in g
