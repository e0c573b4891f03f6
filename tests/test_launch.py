"""Launcher set-up: where the compile cache goes, which config a launcher
runs, and ``chip_smoke.py`` refusing to run without a TPU or the repo."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.config import get_arch
from repro.launch import common

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_follows_env(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert common.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = common.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert common.enable_compile_cache() == path


@pytest.mark.parametrize("smoke,dtype,want", [
    (False, None, "bfloat16"),      # the registered full config's own
    (True, None, "float32"),        # the CPU-sized smoke config
    (False, "float32", "float32"),
    (True, "bfloat16", "bfloat16"),
])
def test_run_config_dtype(smoke, dtype, want):
    spec = get_arch("qwen1.5-0.5b")
    cfg = common.run_config(spec, smoke, dtype)
    assert cfg.param_dtype == cfg.compute_dtype == want
    assert cfg.d_model == (spec.smoke if smoke else spec.model).d_model


def _run_smoke(script: Path, env) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_tpu():
    r = _run_smoke(ROOT / "chip_smoke.py",
                   dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run_smoke(tmp_path / "chip_smoke.py", env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_launcher_runs_the_prediction_depth_and_prints_its_counters(
        capsys, restore_cache_dir, tmp_path, monkeypatch):
    """The smoke preset of joyai-llm-flash (sigmoid routing with the
    correction bias, the multi-token-prediction depth, q-LoRA) trains
    through ``launch/train.py`` and prints the depth's loss, the largest
    bias and the worst load over all experts beside ``gnorm``."""
    from repro.launch import train

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    losses = train.main(["--arch", "joyai-llm-flash", "--smoke", "--steps",
                         "2", "--batch", "2", "--seq", "32", "--log-every",
                         "1", "--ckpt-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr().out
    assert len(losses) == 2 and all(x == x for x in losses)
    step_lines = [line for line in out.splitlines()
                  if line.startswith("step")]
    assert len(step_lines) == 2
    for line in step_lines:
        for label in ("gnorm", "mtp loss", "bias absmax", "max load all"):
            assert label in line, line
    # two steps of the rule from a zero bias: at most 2 x 0.001
    absmax = float(step_lines[-1].split("bias absmax")[1].split()[0])
    assert 0 < absmax <= 0.002 + 1e-9


def test_decode_ignores_the_prediction_depth():
    """Serving runs the model without its depth: with the depth's
    parameters present, a prefill and a decode step give the logits the
    model gives without them."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.models import api

    cfg = dataclasses.replace(get_arch("joyai-llm-flash").smoke,
                              param_dtype="float32", compute_dtype="float32")
    params = api.init_params(jax.random.key(0), cfg)
    assert "mtp" in params
    bare = {k: v for k, v in params.items() if k != "mtp"}
    toks = jnp.asarray([[3, 17, 5, 9]], jnp.int32)
    prefill = jax.jit(lambda p: api.prefill(p, cfg, {"tokens": toks})[0])
    decode = jax.jit(lambda p, s: api.decode_step(
        p, cfg, s, jnp.asarray([11], jnp.int32), jnp.asarray(0, jnp.int32))[0])
    state = api.allocate_decode_state(cfg, 1, 8)
    outs = [(np.asarray(prefill(p)), np.asarray(decode(p, state)))
            for p in (params, bare)]
    assert outs[0][1].shape == (1, cfg.vocab_size)
    assert np.all(np.isfinite(outs[0][1]))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
