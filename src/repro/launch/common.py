"""Process set-up shared by the launchers and ``chip_smoke.py``.

Functions only: importing this module changes no JAX state.
"""
from __future__ import annotations

import dataclasses
import os
import re
from pathlib import Path
from typing import Optional

import jax

from repro.core.config import ArchSpec, ModelConfig

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in the fixed
    ``<repo>/.jax_cache`` (the path is part of the cache key, so it never
    depends on a temporary name, a pid or the time).  Returns the path.

    The key also covers each op's name stack: the layer scopes of
    ``repro.models.scopes`` that a device trace reports, which JAX's
    default key leaves out.  So an executable compiled from code that
    names its layers otherwise is never loaded in its place.  Source
    paths in that metadata are made relative to the checkout, so the key
    does not depend on where the checkout lives."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    return path


def run_config(spec: ArchSpec, smoke: bool,
               dtype: Optional[str] = None) -> ModelConfig:
    """The config a launcher runs: the registered full config in its own
    dtype, or the CPU-sized smoke config in f32; ``dtype`` overrides both."""
    cfg = spec.smoke if smoke else spec.model
    dtype = dtype or ("float32" if smoke else None)
    if dtype:
        cfg = dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)
    return cfg
