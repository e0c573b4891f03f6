"""Replay a graph-mode serving run on the frozen seed engine.

Graph mode (``phase_tasks > 0``) books each phase on a ``TemplateLane``
and computes its per-task schedule lazily, without events.  The replay
injects the same phases task by task into ``tests/reference_engine`` —
each phase when it was submitted, each step of a fused decode burst at
its boundary, with the durations the lane booked — so the seed engine's
live event loop checks the lane's deferred schedule.
"""
import pytest
import reference_engine

from repro.core.sim.engine import Task


def replay_on_seed(sim):
    """The seed engine's result for every phase a finished graph-mode
    ``ServingSimulator`` booked on its lanes."""
    ref = reference_engine.Simulator()
    tid = 0
    for lane in sim._lanes:
        for tpl, t0, durs, bounds in lane.entries:
            steps = [(t0, durs)]
            if bounds is not None:
                steps, prev = [], t0
                for b in bounds:
                    b = float(b)
                    steps.append((prev, lane.step_durs(tpl, b - prev)))
                    prev = b
            for s0, dd in steps:
                tasks = [Task(tid + i, tpl.names[i],
                              tpl.layer_names[tpl.layer_of[i]],
                              tpl.res_names[tpl.res_of[i]], dd[i],
                              deps=tuple(tid + d for d in tpl.deps[i]),
                              kind=tpl.kinds[i])
                         for i in range(tpl.n)]
                ref.at(s0, lambda ts=tasks: [ref.inject(t) for t in ts])
                tid += tpl.n
    return ref.run()


def assert_matches_seed_replay(sim, report, exact=True):
    """Per-task spans (compared on name, start, end: lanes number their
    tasks per lane), resource busy time, layer spans and makespan of
    ``report`` against :func:`replay_on_seed`.  ``exact`` asks for bit
    equality; otherwise values agree to 1e-12 relative."""
    ref = replay_on_seed(sim)
    res = report.sim_result
    spans = sorted((r.task.name, r.start, r.end) for r in res.records)
    ref_spans = sorted((r.task.name, r.start, r.end) for r in ref.records)
    assert len(spans) == len(ref_spans)
    if exact:
        assert spans == ref_spans
        assert res.resource_busy == ref.resource_busy
        assert res.layer_time == ref.layer_time
        assert res.makespan == ref.makespan
        return
    for (n, s, e), (rn, rs, re) in zip(spans, ref_spans):
        assert n == rn
        assert (s, e) == pytest.approx((rs, re), rel=1e-12)
    assert set(res.resource_busy) == set(ref.resource_busy)
    for k, v in ref.resource_busy.items():
        assert res.resource_busy[k] == pytest.approx(v, rel=1e-12)
    assert set(res.layer_time) == set(ref.layer_time)
    for k, v in ref.layer_time.items():
        assert res.layer_time[k] == pytest.approx(v, rel=1e-12)
    assert res.makespan == pytest.approx(ref.makespan, rel=1e-12)
