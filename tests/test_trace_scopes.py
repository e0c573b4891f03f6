"""The program's layer names, as the benchmark's trace reduction reads them.

The model and optimizer mark each layer with a ``jax.named_scope``
(``repro.models.scopes``); ``benchmarks/chip/scopes.py`` puts each device
op of a trace under the innermost such name in its ``tf_op``.  These tests
fail if a refactor drops or renames a scope the readers match, check that
reduction on a small trace of the program's train step recorded on a TPU
v5e (``benchmarks/chip/tests/record_scoped_trace.py``) and by hand, and
check the host spans and counters of ``BatchedServer``.
"""
import dataclasses
import re
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import scopes as bench_scopes  # noqa: E402
import trace_reduce  # noqa: E402

SCOPED = CHIP / "testdata" / "scoped.xplane.pb"
TRAIN_READ = ("attn_core", "attn_proj", "ffn", "head", "optimizer")
DECODE_READ = ("attn_core", "kv_write", "ffn", "head")


# ------------------------------------------- scopes in the compiled program


def _op_names(compiled) -> list:
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


@pytest.fixture(scope="module")
def qwen_smoke():
    from repro.core.config import get_arch

    return dataclasses.replace(get_arch("qwen1.5-0.5b").smoke,
                               param_dtype="float32",
                               compute_dtype="float32")


def test_program_and_benchmark_share_scope_names():
    from repro.models import scopes

    assert tuple(scopes.ALL) == bench_scopes.SCOPES


def test_train_step_names_every_scope_the_readers_match(qwen_smoke):
    """At 2048 tokens attention takes the chunked path, as in the cell."""
    import jax
    import jax.numpy as jnp

    from repro.core.config import OptimizerConfig
    from repro.launch import steps as steps_lib

    opt = OptimizerConfig()
    ps, os_ = steps_lib.train_state_shapes(qwen_smoke, opt)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 2048), jnp.int32)}
    step = jax.jit(steps_lib.make_train_step(qwen_smoke, opt))
    names = _op_names(step.lower(ps, os_, batch).compile())
    found = {bench_scopes.scope_of(n) for n in names}
    assert set(TRAIN_READ) <= found
    assert any("transpose(" in n and bench_scopes.scope_of(n) == "attn_core"
               for n in names), "no backward op under attn_core"
    assert bench_scopes.REST in found


def test_decode_step_names_every_scope_the_readers_match(qwen_smoke):
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as steps_lib
    from repro.models import api

    slots = 4
    state = api.init_decode_state(qwen_smoke, slots, 64)
    tok = jax.ShapeDtypeStruct((slots,), jnp.int32)
    step = jax.jit(steps_lib.make_serve_step(qwen_smoke))
    names = _op_names(step.lower(api.param_shapes(qwen_smoke), state, tok,
                                 tok).compile())
    assert set(DECODE_READ) <= {bench_scopes.scope_of(n) for n in names}


# ------------------------------------------------------ the reduction


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn_proj/attn_core/"
     "closed_call/while/body/exp", "attn_core"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn_proj/cos", "attn_proj"),
    ("jit(train_step)/transpose(jvp(attn_core))/dot_general:", "attn_core"),
    ("jit(train_step)/optimizer/jit(clip)/max", "optimizer"),
    ("jit(serve_step)/while/body/attn_proj/kv_write/dynamic_update_slice",
     "kv_write"),
    ("jit(train_step)/jvp()/while/body/closed_call/rsqrt", "rest"),
    ("jit(train_step)/heads_ffn_x/add", "rest"),
    ("", "rest"),
])
def test_innermost_scope(tf_op, scope):
    assert bench_scopes.scope_of(tf_op) == scope


@pytest.fixture(scope="module")
def scoped():
    tr = trace_reduce.read_xplane(str(SCOPED))
    meta = bench_scopes.read_metadata(str(SCOPED))
    return tr, meta


def test_recorded_trace_is_small():
    assert SCOPED.stat().st_size < 2**20


def test_metadata_names_every_device_op(scoped):
    """The wire-format decoder finds the op names ProfileData gives."""
    from jax.profiler import ProfileData

    _, meta = scoped
    seen = 0
    for plane in ProfileData.from_file(str(SCOPED)).planes:
        if trace_reduce.DEVICE.match(plane.name):
            for line in plane.lines:
                names = {e.name for e in line.events}
                assert names <= set(meta[plane.name]), line.name
                seen += len(names)
    assert seen
    ops = [m for dev in meta.values() for m in dev.values() if "tf_op" in m]
    assert ops and all(isinstance(m.get("flops", 0), int) for m in ops)


def test_backward_ops_are_attributed(scoped):
    _, meta = scoped
    back = {bench_scopes.scope_of(str(m["tf_op"])) for dev in meta.values()
            for m in dev.values() if "transpose(" in str(m.get("tf_op", ""))}
    assert {"attn_core", "ffn", "head"} <= back


def test_scope_times_of_recorded_train_step(scoped):
    tr, meta = scoped
    times = bench_scopes.scope_times(str(SCOPED))
    assert set(TRAIN_READ) | {bench_scopes.REST} <= set(times)
    assert all(v["s"] > 0 for v in times.values())
    assert times["attn_core"]["flops"] > 0
    # an op under no scope lands in rest
    assert any(bench_scopes.scope_of(str(m.get("tf_op", ""))) ==
               bench_scopes.REST for dev in meta.values()
               for m in dev.values() if "tf_op" in m)
    # per-scope times sum to the module's leaf-op time, per call
    leaf = calls = 0.0
    for dev, ops in tr.ops.items():
        mods = [(s, s + d) for n, s, d in tr.modules[dev]
                if trace_reduce.module_name(n) == bench_scopes.TRAIN_MODULE]
        calls += len(mods)
        leaf += sum(d for _, s, d in trace_reduce._leaves(ops)
                    if any(a <= s <= b for a, b in mods))
    assert calls == 3
    assert sum(v["s"] for v in times.values()) == \
        pytest.approx(leaf * 1e-9 / calls, rel=1e-9)
    sec, n = trace_reduce.module_time(trace_reduce.reduce(tr),
                                      bench_scopes.TRAIN_MODULE)
    assert leaf * 1e-9 <= sec * 1.0001
    tops = bench_scopes.top_ops(tr, meta, bench_scopes.TRAIN_MODULE, 2)
    assert set(tops) == set(times)
    for k, rows in tops.items():
        assert 0 < sum(d for _, _, d in rows) <= times[k]["s"] * (1 + 1e-9)
        assert all(bench_scopes.scope_of(tf_op) == k for _, tf_op, _ in rows)


@pytest.mark.parametrize("tf_op,nested", [
    ("jit(train_step)/jvp()/while/body/closed_call/ffn/moe_route/jit(_take)/"
     "gather", "moe_route"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "ffn/moe_experts/cond/branch_0_fun/jit(tgmm)/pallas_call",
     "moe_experts"),
    ("jit(train_step)/jvp()/while/body/closed_call/ffn/dot_general", None),
    ("jit(train_step)/ffn/moe_route/attn_core/dot_general", None),
])
def test_innermost_nested_moe_scope(tf_op, nested):
    import moe_scopes

    assert moe_scopes.nested_scope_of(tf_op) == nested


def test_moe_scopes_lie_inside_ffn():
    """The dropless MoE's ops carry ``moe_route`` or ``moe_experts`` inside
    ``ffn`` (so ``ffn``'s time still holds them); a trace of a program
    without them reads nothing."""
    import jax
    import jax.numpy as jnp
    import moe_scopes

    from repro.core.config import OptimizerConfig, get_arch
    from repro.launch import steps as steps_lib
    from repro.models import scopes

    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").smoke,
                              param_dtype="float32", compute_dtype="float32")
    opt = OptimizerConfig()
    ps, os_ = steps_lib.train_state_shapes(cfg, opt)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
    step = jax.jit(steps_lib.make_train_step(cfg, opt))
    names = _op_names(step.lower(ps, os_, batch).compile())
    inner = [n for n in names if moe_scopes.nested_scope_of(n)]
    assert {moe_scopes.nested_scope_of(n) for n in inner} == \
        set(scopes.NESTED) == set(moe_scopes.NESTED)
    assert all(bench_scopes.scope_of(n) == "ffn" for n in inner)
    assert moe_scopes.nested_times(str(SCOPED)) is None


def test_program_without_scopes_reads_nothing():
    """The older trace, of a jitted function with no scope."""
    assert bench_scopes.scope_times(str(CHIP / "testdata" /
                                        "small.xplane.pb"),
                                    "jit__lambda") is None


def test_idle_and_busy_by_span_by_hand():
    ms = 1e6
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("a", 1 * ms, 2 * ms), ("b", 6 * ms, 1 * ms)]},
        modules={"/device:TPU:0": [("jit_serve_step(1)", 1 * ms, 2 * ms),
                                   ("jit_serve_step(1)", 6 * ms, 1 * ms)]},
        spans=[])
    spans = [("serve.step", 0, 5 * ms), ("serve.decode", 0.5 * ms, 3 * ms),
             ("serve.sample", 3.5 * ms, 1.5 * ms),
             ("serve.step", 5.5 * ms, 2 * ms),
             ("serve.decode", 5.5 * ms, 1.8 * ms)]
    idle = bench_scopes.idle_by_span(tr, spans)
    # gaps: [0, 1] in decode (mid 0.5), [3, 6] mid 4.5 in sample,
    # [7, 7.5] mid 7.25 in decode
    assert idle == {"serve.decode": pytest.approx(0.0015),
                    "serve.sample": pytest.approx(0.003)}
    busy = bench_scopes.busy_in_spans(tr, spans)
    assert busy["serve.decode"]["busy_s"] == pytest.approx(0.003)
    assert busy["serve.decode"]["count"] == 2
    assert busy["serve.sample"]["busy_s"] == 0.0
    assert busy["serve.step"]["span_s"] == pytest.approx(0.007)
    w = bench_scopes.device_window(tr, spans)
    assert w == {"window_s": pytest.approx(0.0075),
                 "busy_s": pytest.approx(0.003)}
    lag = bench_scopes.clock_lag(tr, spans, "serve.decode",
                                 bench_scopes.SERVE_MODULE)
    assert lag["calls"] == 2
    assert lag["dispatch_min_s"] == pytest.approx(0.0005)
    assert lag["return_min_s"] == pytest.approx(0.0003)


def test_timeline_gives_the_innermost_span():
    line = bench_scopes.Timeline([("a", 0, 10), ("b", 2, 3), ("c", 6, 1)])
    assert [line.at(t) for t in (-1, 1, 3, 5.5, 6.5, 9, 11)] == \
        ["outside spans", "a", "b", "a", "c", "a", "outside spans"]


# ------------------------------------------------ BatchedServer on the host


def test_server_spans_nest_and_counters_match_calls(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.launch import serve
    from repro.obs import Probe

    slots, calls = 2, []

    def stub(params, state, tokens, pos):
        calls.append(np.asarray(pos).copy())
        return np.zeros((slots, 8), np.float32), state

    probe = Probe()
    server = serve.BatchedServer(None, slots, 32, decode_fn=stub,
                                 probe=probe)
    reqs = [serve.Request(0, np.array([1, 2, 3], np.int32), 2),
            serve.Request(1, np.array([4, 5], np.int32), 3)]
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs:
            server.admit(r)
        while not all(r.done for r in reqs):
            server.step()
    n_prefill = sum(len(r.prompt) - 1 for r in reqs)
    n_steps = len(calls) - n_prefill
    c = probe.to_metrics()["counters"]
    assert c["serve/prefill_tokens"] == n_prefill
    assert c["serve/decode_steps"] == n_steps == 3
    assert c["serve/slot_steps"] == c["serve/tokens_out"] == \
        sum(len(r.out) for r in reqs) == 5
    assert c["serve/completed"] == 2

    path = trace_reduce.find_xplane(str(tmp_path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("serve.")]
    names = [n for n, _, _ in spans]
    assert set(names) == set(serve.SPANS)
    assert names.count(serve.SPAN_ADMIT) == 2
    assert names.count(serve.SPAN_STEP) == n_steps

    def within(child, parent):
        kids = [(a, b) for n, a, b in spans if n == child]
        outer = [(a, b) for n, a, b in spans if n == parent]
        return len(kids) and all(any(pa <= a and b <= pb for pa, pb in outer)
                                 for a, b in kids)

    assert within(serve.SPAN_PREFILL, serve.SPAN_ADMIT)
    assert within(serve.SPAN_DECODE, serve.SPAN_STEP)
    assert within(serve.SPAN_SAMPLE, serve.SPAN_STEP)


def test_server_without_probe_counts_nothing():
    from repro.launch import serve

    def stub(params, state, tokens, pos):
        return np.zeros((1, 4), np.float32), state

    server = serve.BatchedServer(None, 1, 16, decode_fn=stub)
    server.admit(serve.Request(0, np.array([1, 2], np.int32), 1))
    server.step()
    assert server.probe is None and server._p_steps is None


# ------------------------------------------------ the names and the cache

_KEY_SCRIPT = '''
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
import jax, jax.numpy as jnp
from repro.launch import common
from repro.models import scopes
common.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def layer(x):
    with jax.named_scope(scopes.FFN):
        return x * 2.0

layer(jnp.ones(4)).block_until_ready()
'''


def test_compile_cache_key_holds_the_names_not_the_path(tmp_path):
    """An executable compiled from code that names its layers otherwise
    is never loaded in its place (JAX's default key leaves the names out),
    and two checkouts of one tree share their entries."""
    import os
    import shutil
    import subprocess

    src = Path(__file__).resolve().parents[1] / "src"
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("PYTHONPATH", None)

    def run(tree):
        (tree / "key.py").write_text(_KEY_SCRIPT)
        subprocess.run([sys.executable, str(tree / "key.py")], env=env,
                       check=True, capture_output=True, timeout=120)
        return sorted(f for f in os.listdir(cache) if f.startswith("jit_layer"))

    trees = []
    for name in ("a", "b", "renamed"):
        shutil.copytree(src, tmp_path / name / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
        trees.append(tmp_path / name)
    first = run(trees[0])
    assert len(first) == 1
    assert run(trees[1]) == first
    scopes_py = trees[2] / "src" / "repro" / "models" / "scopes.py"
    scopes_py.write_text(scopes_py.read_text().replace('FFN = "ffn"',
                                                       'FFN = "ffn2"'))
    assert len(run(trees[2])) == 2
