"""Step functions (train / prefill / decode) — the units the launcher jits
and the dry-run lowers.

``make_train_step``/``make_serve_step`` close over (cfg, train cfg) and are
pure: state in, state out, donate-able.  Sharding comes from in_shardings /
out_shardings computed by ``repro.launch.mesh.shardings_for``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig, OptimizerConfig, ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.models import api
from repro.models import layers as L
from repro.models import scopes
from repro.optim import adamw


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    remat: str = "dots") -> Callable:
    def train_step(params, opt_state, batch):
        def loss_of(p):
            loss, metrics = api.loss_fn(p, cfg, batch, remat=remat) \
                if cfg.family != "convnet" else api.loss_fn(p, cfg, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
        metrics = dict(metrics)
        loads = metrics.pop("router_loads", None)
        params, opt_state, opt_metrics = adamw.adamw_update(
            params, grads, opt_state, opt_cfg)
        if loads:
            # sigmoid routers: the correction bias moves towards balance
            # by this step's loads (no gradient reaches it, and AdamW
            # decays no bias, so AdamW has left it as it was)
            with jax.named_scope(scopes.OPTIMIZER):
                params, metrics["moe_bias_absmax"] = \
                    L.rebalance_router_bias(params, loads,
                                            cfg.moe.bias_update_rate)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step


def train_state_shapes(cfg: ModelConfig, opt_cfg: OptimizerConfig):
    """(params, optimizer state) as ShapeDtypeStruct pytrees."""
    params_shapes = api.param_shapes(cfg)
    opt_shapes = jax.eval_shape(
        lambda: adamw.init_opt_state(params_shapes, opt_cfg))
    return params_shapes, opt_shapes


def jit_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, mesh,
                   batch_specs: Dict[str, Any], *, remat: str = "dots",
                   seq_parallel: bool = False) -> Tuple[Callable, Dict]:
    """The train step jitted with the in/out shardings that
    ``mesh.shardings_for`` gives on ``mesh``: params and optimizer state
    keep their shardings from step to step and are donated.  Returns
    ``(jitted, shardings)``; place the state with :func:`init_train_state`.
    """
    params_shapes, opt_shapes = train_state_shapes(cfg, opt_cfg)
    shape = ShapeConfig("train", 0, 0, "train")
    sh = mesh_lib.shardings_for(cfg, shape, mesh, params_shapes, opt_shapes,
                                batch_specs, seq_parallel=seq_parallel)
    jitted = jax.jit(make_train_step(cfg, opt_cfg, remat=remat),
                     in_shardings=(sh["params"], sh["opt_state"], sh["batch"]),
                     out_shardings=(sh["params"], sh["opt_state"], None),
                     donate_argnums=(0, 1))
    return jitted, sh


def init_train_state(key, cfg: ModelConfig, opt_cfg: OptimizerConfig,
                     shardings: Dict[str, Any]):
    """Params and optimizer state created directly in their shardings
    (never gathered on one device first)."""
    params = jax.jit(lambda k: api.init_params(k, cfg),
                     out_shardings=shardings["params"])(key)
    opt_state = jax.jit(lambda p: adamw.init_opt_state(p, opt_cfg),
                        out_shardings=shardings["opt_state"])(params)
    return params, opt_state


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, batch):
        return api.prefill(params, cfg, batch)

    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """One decode step: new token against an existing cache."""

    def serve_step(params, state, tokens, pos):
        return api.decode_step(params, cfg, state, tokens, pos)

    return serve_step


def step_for_shape(cfg: ModelConfig, shape: ShapeConfig,
                   opt_cfg: Optional[OptimizerConfig] = None,
                   remat: str = "dots") -> Tuple[Callable, str]:
    """Returns (step_fn, kind) for a shape cell.

    train  -> train_step(params, opt_state, batch)
    prefill-> prefill_step(params, batch)
    decode -> serve_step(params, state, tokens, pos)
    """
    if shape.mode == "train":
        return make_train_step(cfg, opt_cfg or OptimizerConfig(),
                               remat=remat), "train"
    if shape.mode == "prefill":
        return make_prefill_step(cfg), "prefill"
    return make_serve_step(cfg), "decode"
