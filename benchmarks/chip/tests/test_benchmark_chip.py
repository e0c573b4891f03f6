"""CPU tests of the chip benchmark's harness (no chip needed).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They cover what a CPU can show: the trace reduction on a recorded trace,
the traffic generator, cells found by name, the exits without a chip, the
plain reference against the program's forward pass and train step at the
configurations' small sizes, and the correctness check coming out false
under the control and under each fault a training cell can have.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import flops  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

TESTDATA = CHIP / "testdata"


# ---------------------------------------------------------------- trace


def test_reduce_recorded_trace():
    tr = trace_reduce.Trace.from_json(
        (TESTDATA / "small_trace.json").read_text())
    red = trace_reduce.reduce(tr)
    assert 0 < red["busy_s"] < red["window_s"]
    sec, calls = trace_reduce.module_time(red, r"jit_")
    assert calls == 3 and 0 < sec <= red["busy_s"] * 1.001
    assert red["device_ops"] and all(d > 0 for _, d in red["device_ops"])
    assert red["idle_gaps"] and red["idle_gaps"][0][1] > 0
    names = {n for n, _ in red["idle_gaps"]}
    assert names <= {"train_step", "outside spans"}


def test_read_xplane_matches_recorded_events():
    pb = TESTDATA / "small.xplane.pb"
    a = trace_reduce.read_xplane(str(pb))
    b = trace_reduce.Trace.from_json(
        (TESTDATA / "small_trace.json").read_text())
    assert trace_reduce.reduce(a) == trace_reduce.reduce(b)


def test_reduce_by_hand():
    """Two devices, ops at known times: busy is the union per device,
    averaged; gaps are named by the innermost host span."""
    ms = 1e6
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("a", 0, 2 * ms), ("b", 1 * ms, 2 * ms),
                               ("a", 6 * ms, 4 * ms)],
             "/device:TPU:1": [("a", 0, 10 * ms)]},
        modules={"/device:TPU:0": [("jit_step(3)", 0, 3 * ms),
                                   ("jit_step(3)", 6 * ms, 4 * ms)],
                 "/device:TPU:1": [("jit_step(3)", 0, 10 * ms)]},
        spans=[("train_step", 0, 10 * ms), ("gen", 3 * ms, 3 * ms)])
    red = trace_reduce.reduce(tr)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx((0.007 + 0.010) / 2)
    assert red["idle_gaps"] == [["gen", pytest.approx(0.003)]]
    assert trace_reduce.module_time(red, "jit_step") == \
        (pytest.approx(0.0085), 1.5)
    assert red["device_ops"][0][0] == "a"


def test_device_ops_count_leaves_only():
    ms = 1e6
    tr = trace_reduce.Trace(
        ops={"/device:TPU:0": [("%while.1 = (s32[]) while()", 0, 10 * ms),
                               ("%fusion.2 = f32[8] fusion()", 1 * ms,
                                4 * ms),
                               ("%fusion.3 = f32[8] fusion()", 6 * ms,
                                3 * ms)]},
        modules={"/device:TPU:0": []}, spans=[("step", 0, 10 * ms)])
    red = trace_reduce.reduce(tr)
    assert red["device_ops"] == [["%fusion.2 f32[8]", pytest.approx(0.004)],
                                 ["%fusion.3 f32[8]", pytest.approx(0.003)]]
    assert red["busy_s"] == pytest.approx(0.010)


def test_reduce_empty_trace_reads_nothing():
    assert trace_reduce.reduce(trace_reduce.Trace()) == {}


# -------------------------------------------------------------- traffic


MIX = json.loads((CHIP / "traffic" / "decode_chat.json").read_text())


def test_same_seed_same_schedule():
    a = traffic_gen.serve_schedule(MIX, 2**31 + 5, 60.0, 1000)
    b = traffic_gen.serve_schedule(MIX, 2**31 + 5, 60.0, 1000)
    assert [(p.due, p.max_new, p.prompt.tolist()) for p in a] == \
        [(p.due, p.max_new, p.prompt.tolist()) for p in b]


def test_seeds_share_the_work():
    """Two seeds give the same lengths and gaps in another order."""
    a = traffic_gen.serve_schedule(MIX, 11, 1e4, 1000)
    b = traffic_gen.serve_schedule(MIX, 12, 1e4, 1000)
    n = min(len(a), len(b)) - 50
    la = sorted(len(p.prompt) for p in a[:n])
    lb = sorted(len(p.prompt) for p in b[:n])
    assert abs(np.mean(la) - np.mean(lb)) < 0.05 * np.mean(la)
    assert [p.due for p in a[:5]] != [p.due for p in b[:5]]
    assert abs(len(a) - len(b)) < 0.05 * len(a)


def test_lengths_follow_the_mix():
    s = traffic_gen.serve_schedule(MIX, 3, 5e3, 1000)
    p = np.array([len(x.prompt) for x in s])
    o = np.array([x.max_new for x in s])
    assert p.min() >= 8 and p.max() <= 256 and o.min() >= 32 \
        and o.max() <= 1024
    assert 40 < p.mean() < 56 and 220 < o.mean() < 290
    rate = len(s) / s[-1].due
    assert rate == pytest.approx(MIX["arrivals"]["rate"], rel=0.1)


def test_train_batches_seeded_and_distinct():
    mix = json.loads((CHIP / "traffic" / "train_4k.json").read_text())
    mix = dict(mix, seq_len=64)
    a = traffic_gen.train_batch(mix, 2**33 + 1, 0, 2, 500)
    assert np.array_equal(a, traffic_gen.train_batch(mix, 2**33 + 1, 0, 2,
                                                     500))
    assert not np.array_equal(a, traffic_gen.train_batch(mix, 2**33 + 1, 1,
                                                         2, 500))
    assert a.dtype == np.int32 and a.shape == (2, 64) and a.max() < 500


# ------------------------------------------------------- found by name


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    """A new cell is new files plus BENCHMARK.json entries: the harness
    finds the configuration, mix, limits, reader, reference and counts by
    name alone."""
    for d in ("configs", "traffic", "limits", "metrics", "references",
              "counts"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny-model.json").write_text(
        json.dumps({"name": "tiny-model", "hidden_size": 8}))
    (tmp_path / "traffic" / "bursty_mix.json").write_text(
        json.dumps({"kind": "serve", "rate": 3}))
    (tmp_path / "limits" / "tiny-model.bursty_mix.json").write_text(
        json.dumps({"limits": {"gap": 0.5}}))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx\n")
    (tmp_path / "references" / "tiny-model.py").write_text(
        "from reference import *  # noqa: F401,F403\nOWN = 'ref'\n")
    (tmp_path / "counts" / "tiny-model.py").write_text(
        "def train_step_flops(c, batch, seq):\n    return 7.0\n")
    bench = {"workloads": [{"name": "tiny-model.bursty_mix",
                            "config": "tiny-model", "traffic": "bursty_mix",
                            "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "e2e", "workloads":
                             ["tiny-model.bursty_mix"]},
                            {"name": "setup_s"},
                            {"name": "other", "workloads": ["elsewhere"]}],
             "per_layer": [{"name": "new_metric", "moves": "e2e"},
                           {"name": "x", "moves": "other"}]}
    cell = harness.find_cell("tiny-model.bursty_mix", bench, here=tmp_path)
    assert cell.config["hidden_size"] == 8 and cell.traffic["rate"] == 3
    assert cell.limits["limits"] == {"gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["e2e", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert harness.metric_reader("new_metric", here=tmp_path)(4) == 8.0
    ref = harness.reference_for(cell.config, cell.here)
    assert ref.OWN == "ref" and callable(ref.RefConfig.from_file)
    assert harness.counts_for(cell.config, cell.here).train_step_flops(
        cell.config, 1, 8) == 7.0


def test_every_listed_file_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        assert cell.limits["limits"]
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()


def test_program_config_takes_published_sizes():
    conf = json.loads((CHIP / "configs" / "qwen1.5-0.5b.json").read_text())
    cfg = harness.program_config(conf, {"param_dtype": "float32"})
    assert cfg.attention.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert cfg.param_dtype == "float32" and cfg.compute_dtype == "bfloat16"
    with pytest.raises(harness.BenchError):
        harness.program_config(dict(conf, hidden_size=1023), {})


# ------------------------------------------------------------ no chip


def _run_bench(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "qwen1.5-0.5b.train_4k", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_bench_fails_without_tpu():
    r = _run_bench(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "{" not in r.stdout


def test_bench_fails_outside_a_full_checkout(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run_bench(tmp_path)
    assert r.returncode != 0 and "{" not in r.stdout


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    peaks = json.loads((CHIP / "peaks.json").read_text())
    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.check_device(1, peaks)
    Dev.device_kind = "TPU v5 lite"
    with pytest.raises(harness.BenchError, match="chips"):
        harness.check_device(4, peaks)
    assert harness.check_device(1, peaks)[0].device_kind == "TPU v5 lite"


# ---------------------------------------------------------- FLOP counts


def test_flop_counts_of_qwen():
    c = json.loads((CHIP / "configs" / "qwen1.5-0.5b.json").read_text())
    n = flops.total_params(c)
    assert 463e6 < n < 465e6          # 464 M parameters
    assert flops.kv_bytes_per_token(c) == 96 * 1024
    per_tok = flops.train_step_flops(c, 1, 4096) / 4096
    assert per_tok == pytest.approx(
        6 * flops.matmul_params(c)
        + 3 * flops.attention_pair_flops(c) * 4097 / 2)
    assert flops.decode_step_flops(c, [0]) == 2 * flops.matmul_params(c)


def test_flop_counts_of_granite_count_active_experts():
    c = json.loads((CHIP / "configs" /
                    "granite-moe-1b-a400m.json").read_text())
    assert 1.3e9 < flops.total_params(c) < 1.4e9
    assert 0.38e9 < flops.matmul_params(c) < 0.46e9


# ----------------------------------------- the reference and the program


def _smoke(arch, traffic=None):
    from repro.core.config import get_arch

    cfg = get_arch(arch).smoke
    a = cfg.attention
    conf = {"name": arch + "-smoke", "arch": arch, "size": "smoke",
            "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "vocab_size": cfg.vocab_size, "rope_theta": 1e6,
            "rms_norm_eps": 1e-6, "attention_bias": a.qkv_bias,
            "tie_word_embeddings": True, "serve_slots": 8}
    if cfg.moe:
        conf.update(num_local_experts=cfg.moe.num_experts,
                    num_experts_per_tok=cfg.moe.num_experts_per_tok,
                    intermediate_size=cfg.moe.d_ff_expert)
    else:
        conf["intermediate_size"] = cfg.d_ff
    return conf


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "granite-moe-1b-a400m"])
def test_reference_matches_program_forward(arch):
    import jax
    import jax.numpy as jnp
    from repro.models import api

    import weights

    conf = _smoke(arch)
    reference = harness.reference_for(conf)
    cfg = harness.program_config(conf, {"param_dtype": "float32",
                                        "compute_dtype": "float32"})
    if cfg.moe:     # the program's dropless option, as the reference is
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=0.0))
    W = weights.make_params(api.param_shapes(cfg), 2**31 + 77)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, 600), jnp.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = api.forward(W, cfg, {"tokens": toks[None]})
    ref = reference.logits_at(reference.highest_mm,
                              reference.RefConfig.from_file(conf), W, toks,
                              jnp.arange(600))
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(ref),
                               atol=2e-5 * float(jnp.abs(ref).max()))


# --------------------------------------------- correct, control, faults


def _train_cell():
    limits = json.loads(
        (CHIP / "limits" / "qwen1.5-0.5b.train_4k.json").read_text())
    mix = json.loads((CHIP / "traffic" / "train_4k.json").read_text())
    mix = dict(mix, seq_len=256, batch=2)
    return harness.Cell("qwen-smoke.train", 1, _smoke("qwen1.5-0.5b"), mix,
                        limits, [{"name": "train_tok_s", "unit": "tokens/s"},
                                 {"name": "setup_s", "unit": "s"}], [])


def _run(cell, break_step=None):
    import jax

    import bench

    class Args:
        workload, seed, seconds, trace = cell.name, 2**31 + 3, 0.5, 0
    return bench.run_cell(Args, cell=cell, devices=jax.devices(),
                          break_step=break_step)[0]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    yield
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


def test_sound_train_run_is_correct(cache_dir):
    """The plumbing of a sound run.  At this small size on the CPU the
    readings differ from the cell's (its limits were set on the chip at
    its own size, PERF.md section 2), so the limits here are 3x wider."""
    cell = _train_cell()
    cell.limits = {"limits": {k: 3 * v for k, v in
                              cell.limits["limits"].items()}}
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss", "grad", "change"}
    assert list(res)[-1] == "checks"


def _unchanged_state(step, cfg, opt_cfg, sh):
    """A step that returns its state unchanged.  The program's step
    donates its inputs, so the state it returns is a copy taken before."""
    import jax
    import jax.numpy as jnp

    def f(p, o, b):
        keep = jax.tree.map(jnp.copy, (p, o))
        _, _, m = step(p, o, b)
        return keep[0], keep[1], m
    return f


def test_state_left_unchanged_is_not_correct(cache_dir):
    res = _run(_train_cell(), break_step=_unchanged_state)
    assert not res["correct"]
    assert res["checks"]["change"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_batch_is_not_correct(cache_dir):
    import calibrate

    res = _run(_train_cell(), break_step=calibrate.half_batch)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(cache_dir):
    """The reference in float8 in the program's place fails the limits."""
    from repro.models import api

    import correctness
    import train_loop

    cell = _train_cell()
    cfg = harness.program_config(cell.config, cell.traffic)
    shapes = api.param_shapes(cfg)
    batches = [traffic_gen.train_batch(cell.traffic, 5, i, 2,
                                       cfg.vocab_size) for i in range(3)]
    ref = train_loop.reference_readings(cell, shapes, 5, batches)
    ctrl = train_loop.reference_readings(
        cell, shapes, 5, batches,
        mm=harness.reference_for(cell.config).fp8_mm)
    ok, rows = correctness.judge(correctness.train_readings(ctrl, ref),
                                 cell.limits["limits"])
    assert not ok, rows
