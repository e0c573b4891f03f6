"""Observability layer: MetricSeries math, trace-event schema validity,
instrumented-vs-uninstrumented bit-parity across every hook point, run
bundles, and the ``repro.obs.compare`` regression-diff CLI."""
import json

import numpy as np
import pytest

from repro.core.sim.engine import (DynamicSimulator, ResourceSpec, Task,
                                   simulate_static)
from repro.core.sim.trace import (ascii_gantt, chrome_trace,
                                  serving_chrome_trace, serving_trace_builder,
                                  trace_builder)
from repro.obs import (HistogramSummary, MetricSeries, Probe, TraceBuilder,
                       get_probe, merge_series, set_probe, validate_trace,
                       write_bundle, load_bundle)
from repro.obs.compare import diff, flatten, main as compare_main
from repro.serve_sim import (ContinuousBatchingScheduler, LengthDist,
                             MonteCarloServingSimulator, ServingCostModel,
                             ServingSimulator, poisson_workload,
                             poisson_workload_batch)

TOY = ServingCostModel(name="toy", prefill_fixed=1e-3, prefill_per_token=2e-5,
                       decode_fixed=2e-3, decode_per_token=5e-4,
                       decode_per_ctx_token=1e-7)
PROMPT = LengthDist(mean=128, cv=0.5)
OUTPUT = LengthDist(mean=32, cv=0.5)


def toy_poisson(n=120, rate=30.0, seed=0):
    return poisson_workload(rate, n, prompt=PROMPT, output=OUTPUT, seed=seed)


# ---------------------------------------------------------------------------
# MetricSeries / merge / histogram math
# ---------------------------------------------------------------------------


def test_series_records_samples_in_order():
    s = MetricSeries("x", kind="counter")
    for i in range(5):
        s.sample(float(i), float(i * 2))
    assert len(s) == 5
    np.testing.assert_allclose(s.t, [0, 1, 2, 3, 4])
    np.testing.assert_allclose(s.values, [0, 2, 4, 6, 8])
    assert s.value_at(2.5) == 4.0
    assert s.value_at(-1.0) == 0.0


def test_series_decimation_keeps_every_kth_and_flushes_last():
    s = MetricSeries("x", kind="counter", sample_every=4)
    for i in range(10):
        s.sample(float(i), float(i))
    # keeps every 4th update (i=3, i=7); the pending i=9 arrives on flush
    assert len(s) == 2
    s.flush()
    assert len(s) == 3
    assert s.t[-1] == 9.0 and s.values[-1] == 9.0
    s.flush()                               # idempotent
    assert len(s) == 3


def test_series_roundtrip():
    s = MetricSeries("q", kind="gauge", unit="requests")
    s.sample(0.0, 1.0)
    s.sample(2.0, 3.0)
    d = s.to_dict()
    r = MetricSeries.from_dict("q", d)
    assert r.name == "q" and r.unit == "requests"
    np.testing.assert_allclose(r.t, s.t)
    np.testing.assert_allclose(r.values, s.values)


def test_merge_series_mean_and_ci():
    members = []
    for v in (1.0, 2.0, 3.0):
        s = MetricSeries("x", kind="gauge")
        s.sample(0.0, v)
        s.sample(10.0, v)
        members.append(s)
    m = merge_series(members, grid_points=8)
    assert m.n_members == 3
    np.testing.assert_allclose(m.mean, np.full(8, 2.0))
    # 95% CI half-width = 1.96 * sample std / sqrt(K), std({1,2,3}) = 1
    expect = 1.96 * np.std([1.0, 2.0, 3.0], ddof=1) / np.sqrt(3)
    np.testing.assert_allclose(m.ci_hi - m.mean, np.full(8, expect))
    np.testing.assert_allclose(m.mean - m.ci_lo, np.full(8, expect))
    assert m.t[0] == 0.0 and m.t[-1] == 10.0


def test_merge_series_step_interpolation():
    a = MetricSeries("x", kind="counter")
    a.sample(0.0, 0.0)
    a.sample(5.0, 10.0)
    m = merge_series([a], grid_points=11)
    # step function: holds 0 until t=5, then 10 (no linear ramp)
    assert m.mean[m.t < 5.0].max() == 0.0
    assert m.mean[-1] == 10.0


def test_histogram_summary_stats():
    h = HistogramSummary("lat", unit="s")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100
    assert h.min == 1.0 and h.max == 100.0
    assert h.total == pytest.approx(5050.0)
    assert h.percentile(50) == pytest.approx(50.5, rel=0.05)
    d = h.to_dict()
    assert d["count"] == 100


# ---------------------------------------------------------------------------
# Probe semantics
# ---------------------------------------------------------------------------


def test_probe_handles_are_memoized():
    p = Probe("t")
    assert p.counter("a") is p.counter("a")
    assert p.gauge("g") is p.gauge("g")
    assert p.histogram("h") is p.histogram("h")
    assert p.child("c") is p.child("c")


def test_probe_counter_records_running_total():
    p = Probe("t")
    c = p.counter("q")
    c.add(0.0, 2)
    c.add(1.0, -1)
    np.testing.assert_allclose(c.series.values, [2.0, 1.0])
    assert p.to_metrics()["counters"]["q"] == 1.0


def test_probe_merged_child_series():
    p = Probe("mc")
    for seed, v in enumerate((10.0, 20.0)):
        g = p.child(f"seed{seed}").gauge("serve/queue_depth")
        g.set(0.0, v)
        g.set(1.0, v)
    merged = p.merged_child_series(grid_points=4)
    assert "serve/queue_depth" in merged
    np.testing.assert_allclose(merged["serve/queue_depth"].mean,
                               np.full(4, 15.0))


def test_global_probe_set_and_restore():
    p = Probe("g")
    prev = set_probe(p)
    try:
        assert get_probe() is p
    finally:
        set_probe(prev)
    assert get_probe() is prev


# ---------------------------------------------------------------------------
# trace-event schema
# ---------------------------------------------------------------------------


def _static_tasks():
    return [Task(0, "dma", "L0", "dma0", 2.0),
            Task(1, "mm", "L0", "nce", 3.0, deps=(0,)),
            Task(2, "mm2", "L1", "nce", 1.0, deps=(1,))]


def test_chrome_trace_validates():
    doc = chrome_trace(DynamicSimulator(_static_tasks()).run())
    assert validate_trace(doc) == []
    events = json.loads(doc)["traceEvents"]
    assert any(e["ph"] == "X" for e in events)
    assert any(e["ph"] == "M" for e in events)


def test_serving_trace_validates_and_has_queue_counter():
    rep = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(),
                           slots=4).run()
    doc = serving_chrome_trace(rep)
    assert validate_trace(doc) == []
    events = json.loads(doc)["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"]
    assert counters, "queue-depth counter track missing"
    # closed at the makespan: final counter sample reaches the duration
    assert max(e["ts"] for e in counters) == pytest.approx(
        rep.duration * 1e6, rel=1e-6)
    # depth never negative
    assert min(e["args"]["requests"] for e in counters) >= 0


def test_validate_trace_flags_malformed():
    bad = {"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 0, "ts": 0.0},          # missing dur
        {"ph": "C", "pid": 0, "name": "c", "ts": 1.0,
         "args": {"v": 1}},
        {"ph": "C", "pid": 0, "name": "c", "ts": 0.5,        # ts regressed
         "args": {"v": 2}},
    ]}
    problems = validate_trace(bad)
    assert problems
    assert any("dur" in p for p in problems)
    assert any("backwards" in p for p in problems)


def test_trace_builder_counter_tracks_and_probe_export():
    p = Probe("run")
    c = p.counter("serve/queue_depth", unit="requests")
    c.add(0.0, 3)
    c.add(0.5, -1)
    p.span("phase", 0.0, 0.25, track="phases")
    tb = TraceBuilder()
    tb.add_probe(p, end_time=1.0)
    assert validate_trace(tb.events) == []
    tracks = tb.counter_tracks()
    assert any(name == "serve/queue_depth" for _, name in tracks)
    # final value re-emitted at end_time
    cs = [e for e in tb.events if e.get("ph") == "C"]
    assert max(e["ts"] for e in cs) == pytest.approx(1.0 * 1e6)


# ---------------------------------------------------------------------------
# bit-parity: instrumentation changes what is recorded, never what happens
# ---------------------------------------------------------------------------


def _shared_tasks():
    shared = {"net": ResourceSpec("net", mode="shared")}
    tasks = [Task(i, f"x{i}", "L", "net", 1e-3) for i in range(6)]
    tasks += [Task(6, "c", "L", "cpu", 2e-3, deps=(0, 1))]
    return tasks, shared


def test_simulator_parity_with_probe():
    tasks, shared = _shared_tasks()
    base = DynamicSimulator(tasks, resources=dict(shared)).run()
    p = Probe("on")
    inst = DynamicSimulator(tasks, resources=dict(shared), probe=p).run()
    assert inst.makespan == base.makespan
    assert [(r.task.tid, r.start, r.end) for r in inst.records] == \
           [(r.task.tid, r.start, r.end) for r in base.records]
    assert p.all_series()                       # something was recorded


def test_simulate_static_parity_with_probe():
    tasks = _static_tasks()
    base = simulate_static(tasks)
    p = Probe("on")
    inst = simulate_static(tasks, probe=p)
    assert inst.makespan == base.makespan
    assert [(r.start, r.end) for r in inst.records] == \
           [(r.start, r.end) for r in base.records]
    series = p.all_series()
    assert any(name.startswith("static/") for name in series)


def test_dynamic_simulator_parity_with_probe():
    def build(probe=None):
        sim = DynamicSimulator(resources={"r": ResourceSpec("r")},
                               probe=probe)
        sim.at(0.0, lambda: sim.inject(Task(0, "a", "L", "r", 1.0)))
        sim.at(0.5, lambda: sim.inject(Task(1, "b", "L", "r", 1.0)))
        return sim.run()

    base = build()
    p = Probe("on")
    inst = build(probe=p)
    assert inst.makespan == base.makespan
    assert p.to_metrics()["counters"].get("engine/fifo_completions") == 2.0


def test_serving_parity_with_probe():
    base = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(),
                            replicas=2, slots=4).run()
    p = Probe("on")
    inst = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(),
                            replicas=2, slots=4, probe=p).run()
    assert inst.duration == base.duration
    assert inst.ttft.p99 == base.ttft.p99
    assert list(inst.requests) == list(base.requests)
    series = p.all_series()
    assert "serve/queue_depth" in series
    # queue-depth track closed at the makespan
    assert series["serve/queue_depth"].t[-1] == pytest.approx(base.duration)


def test_monte_carlo_parity_with_probe_and_seed_children():
    batch = poisson_workload_batch(30.0, 80, prompt=PROMPT, output=OUTPUT,
                                   seeds=3)
    base = MonteCarloServingSimulator(
        TOY, ContinuousBatchingScheduler, batch, slots=4).run()
    p = Probe("mc")
    inst = MonteCarloServingSimulator(
        TOY, ContinuousBatchingScheduler, batch, slots=4, probe=p).run()
    for a, b in zip(inst.reports, base.reports):
        assert a.duration == b.duration
        assert a.ttft.p99 == b.ttft.p99
    assert len(p.children) == 3                 # one child per seed
    merged = p.merged_child_series()
    assert "serve/queue_depth" in merged
    assert merged["serve/queue_depth"].n_members == 3


def test_dse_probe_counters():
    from repro.core.config import get_arch
    from repro.core.dse import DesignSpaceExplorer
    from repro.core.hw import virtex7_nce_system
    from repro.core.taskgraph.builders import convnet_ops

    cfg = get_arch("dilated-vgg").model
    p = Probe("dse")
    dse = DesignSpaceExplorer({"vgg": convnet_ops(cfg)}, probe=p)
    dse.explore({"base": virtex7_nce_system()}, keep=1)
    m = p.to_metrics()
    assert m["counters"]["dse/compiles"] == 1.0
    assert m["counters"]["dse/points_done"] == 1.0
    assert m["counters"]["dse/confirmed"] == 1.0
    assert "dse/point_seconds" in m["histograms"]
    assert [s[0] for s in p.all_spans()] == ["sweep[roofline]",
                                             "explore[roofline->des]"]


def test_worker_pool_reports_into_global_probe():
    from repro.core.parallel import parallel_map

    p = Probe("pool")
    prev = set_probe(p)
    try:
        out = parallel_map(len, [[1, 2], [3], [4, 5, 6]], workers=2)
    finally:
        set_probe(prev)
    assert out == [2, 1, 3]
    m = p.to_metrics()
    assert m["counters"]["pool/jobs"] == 3.0
    assert "pool/job_seconds" in m["histograms"]


def test_ascii_gantt_narrow_width_does_not_raise():
    res = DynamicSimulator(_static_tasks()).run()
    for w in (1, 5, 11, 12):
        out = ascii_gantt(res, width=w)
        assert "compute" in out or "#" in out


# ---------------------------------------------------------------------------
# bundles + compare CLI
# ---------------------------------------------------------------------------


def test_write_bundle_roundtrip(tmp_path):
    p = Probe("bundle")
    rep = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(),
                           slots=4, probe=p).run()
    path = write_bundle("smoke", out_dir=str(tmp_path), report=rep, probe=p)
    assert path == str(tmp_path / "smoke")
    assert (tmp_path / "smoke" / "trace.json").exists()
    assert (tmp_path / "smoke" / "metrics.json").exists()
    assert (tmp_path / "smoke" / "summary.md").exists()
    doc = json.loads((tmp_path / "smoke" / "trace.json").read_text())
    assert validate_trace(doc) == []
    loaded = load_bundle(str(tmp_path / "smoke"))
    assert loaded["name"] == "smoke"
    assert loaded["report"]["n_requests"] == rep.n_requests
    assert loaded["report"]["throughput_rps"] > 0


def test_flatten_and_diff_directions():
    a = {"report": {"throughput_rps": 100.0, "ttft": {"p99": 0.5}}}
    b = {"report": {"throughput_rps": 80.0, "ttft": {"p99": 0.6}}}
    fa, fb = flatten(a), flatten(b)
    assert fa["report.throughput_rps"] == 100.0
    rows = diff(fa, fb, threshold_pct=5.0)
    by_key = {r[0]: r for r in rows}
    assert by_key["report.throughput_rps"][4] == "regression"
    assert by_key["report.ttft.p99"][4] == "regression"


def test_compare_cli_exit_codes(tmp_path):
    good = {"report": {"throughput_rps": 100.0}}
    bad = {"report": {"throughput_rps": 50.0}}
    pa = tmp_path / "a.json"
    pb = tmp_path / "b.json"
    pa.write_text(json.dumps(good))
    pb.write_text(json.dumps(bad))
    assert compare_main([str(pa), str(pa)]) == 0
    assert compare_main([str(pa), str(pb), "--fail-on-regression"]) == 1


def test_compare_reads_bundle_dir_and_bench_file(tmp_path):
    p = Probe("b")
    rep = ServingSimulator(TOY, ContinuousBatchingScheduler, toy_poisson(),
                           slots=4, probe=p).run()
    write_bundle("run_a", out_dir=str(tmp_path), report=rep, probe=p)
    bench = {"pr": 7, "current": {
        "serve": {"throughput_rps": rep.throughput_rps * 2}}}
    bench_path = tmp_path / "BENCH_test.json"
    bench_path.write_text(json.dumps(bench))
    # bundle vs BENCH falls back to basename matching; must not raise
    rc = compare_main([str(tmp_path / "run_a"), str(bench_path)])
    assert rc == 0
