"""Mesh construction + sharding assignment for the production topology.

``make_production_mesh`` builds the grading meshes:
  single-pod:  (16, 16)        axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") = 512 chips

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as sh
from repro.core.config import (MeshConfig, ModelConfig, OptimizerConfig,
                               ShapeConfig)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model annotates activations with sharding constraints
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_elastic_mesh(num_devices: int, model_parallel: int = 16) -> Mesh:
    """Best-effort (data, model) mesh for an arbitrary surviving device
    count (elastic scaling after failures)."""
    while model_parallel > 1 and num_devices % model_parallel:
        model_parallel //= 2
    data = num_devices // model_parallel
    devs = np.asarray(jax.devices()[:data * model_parallel])
    return Mesh(devs.reshape(data, model_parallel), ("data", "model"))


def mesh_name(mesh: Mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)


# ---------------------------------------------------------------------------
# Sharding assignment per step kind
# ---------------------------------------------------------------------------


def batch_shardings(cfg: ModelConfig, batch_specs: Dict[str, Any],
                    mesh: Mesh) -> Dict[str, Any]:
    out = {}
    for k, v in batch_specs.items():
        if k == "tokens":
            logical = ("batch",) + (None,) * (len(v.shape) - 1)
        elif k in ("prefix_embeds", "frames"):
            logical = ("batch", None, None)
        elif k == "image":
            logical = ("batch", None, None, None)
        elif k == "labels":
            logical = ("batch",) + (None,) * (len(v.shape) - 1)
        elif k == "pos":
            logical = ()
        else:
            logical = (None,) * len(v.shape)
        out[k] = sh.input_pspec(v.shape, logical, mesh)
    return out


def shardings_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                  params_shapes, opt_shapes=None,
                  input_specs: Optional[Dict[str, Any]] = None,
                  seq_parallel: bool = False) -> Dict[str, Any]:
    """in/out sharding pytrees for the step function of this shape cell."""
    param_sh = sh.param_shardings(params_shapes, mesh)
    repl = NamedSharding(mesh, P())
    out: Dict[str, Any] = {"params": param_sh}
    if opt_shapes is not None:
        opt_sh = {
            "m": sh.param_shardings(opt_shapes["m"], mesh),
            "v": sh.param_shardings(opt_shapes["v"], mesh),
            "step": repl,
        }
        if "ef" in opt_shapes:
            opt_sh["ef"] = sh.param_shardings(opt_shapes["ef"], mesh)
        out["opt_state"] = opt_sh
    if input_specs is not None:
        if shape.mode == "decode":
            out["state"] = sh.state_shardings(input_specs["state"], mesh,
                                              seq_parallel=seq_parallel)
            out["tokens"] = sh.input_pspec(input_specs["tokens"].shape,
                                           ("batch",), mesh)
            out["pos"] = repl
        else:
            batch = {k: v for k, v in input_specs.items()}
            out["batch"] = batch_shardings(cfg, batch, mesh)
    return out
