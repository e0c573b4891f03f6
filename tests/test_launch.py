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
