"""AdamW optimizer (decoupled weight decay), schedules, global-norm clipping
and int8 gradient compression with error feedback — pure JAX, pytree-based.

Optimizer state is a pytree parallel to params:
  {"m": f32 tree, "v": f32 tree, "step": scalar, ("ef": error-feedback tree)}
so it shards exactly like the parameters (see repro.sharding).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import OptimizerConfig
from repro.models import scopes

Params = Any


def lr_schedule(cfg: OptimizerConfig, step: jnp.ndarray) -> jnp.ndarray:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        decay = 1.0
    elif cfg.schedule == "linear":
        frac = jnp.clip((step - cfg.warmup_steps)
                        / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
        decay = 1.0 - frac
    else:  # cosine
        frac = jnp.clip((step - cfg.warmup_steps)
                        / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return cfg.lr * warm * decay


def init_opt_state(params: Params, cfg: OptimizerConfig) -> Dict:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = {
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg.grad_compression == "int8_ef":
        state["ef"] = jax.tree.map(zeros32, params)
    return state


def global_norm(tree: Params) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads: Params, max_norm: float
                        ) -> Tuple[Params, jnp.ndarray]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


# ---------------------------------------------------------------------------
# int8 gradient compression with error feedback.
#
# Quantize grads to int8 with a per-tensor scale before the cross-replica
# reduction; the quantization residual is fed back into the next step
# (error feedback keeps convergence).  Under `jax.grad` the reduction is
# inserted by GSPMD, so we model compression as quantize->dequantize around
# the mean — on a real fleet this pairs with an int8 all-reduce custom call;
# the EF mechanics and convergence behaviour are identical.
# ---------------------------------------------------------------------------


def compress_decompress(g: jnp.ndarray, ef: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    gf = g.astype(jnp.float32) + ef
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127)
    deq = q * scale
    return deq, gf - deq


def apply_compression(grads: Params, state: Dict) -> Tuple[Params, Dict]:
    if "ef" not in state:
        return grads, state
    out = jax.tree.map(compress_decompress, grads, state["ef"])
    deq = jax.tree.map(lambda t: t[0], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    ef = jax.tree.map(lambda t: t[1], out,
                      is_leaf=lambda x: isinstance(x, tuple))
    new_state = dict(state)
    new_state["ef"] = ef
    return deq, new_state


import re as _re

_DECAY_EXEMPT = (r"norm", r"/scale$", r"/bias$", r"/b$", r"/mu_", r"/w0$",
                 r"/A_log$", r"/D$", r"/u$")


def _decay_mask(path: str) -> float:
    return 0.0 if any(_re.search(t, path) for t in _DECAY_EXEMPT) else 1.0


def _paths(tree, prefix="") -> Any:
    if isinstance(tree, dict):
        return {k: _paths(v, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_paths(v, f"{prefix}/{i}")
                          for i, v in enumerate(tree))
    return prefix


@jax.named_scope(scopes.OPTIMIZER)
def adamw_update(params: Params, grads: Params, state: Dict,
                 cfg: OptimizerConfig) -> Tuple[Params, Dict, Dict]:
    """One AdamW step.  Returns (params, state, metrics)."""
    grads, state = apply_compression(grads, state)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    b1, b2, eps = cfg.b1, cfg.b2, cfg.eps
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    paths = _paths(params)

    def upd(p, g, m, v, path):
        gf = g.astype(jnp.float32)
        m_new = b1 * m + (1 - b1) * gf
        v_new = b2 * v + (1 - b2) * jnp.square(gf)
        mhat = m_new / bc1
        vhat = v_new / bc2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        delta = delta + cfg.weight_decay * _decay_mask(path) \
            * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return p_new.astype(p.dtype), m_new, v_new

    out = jax.tree.map(upd, params, grads, state["m"], state["v"], paths)
    p_new = jax.tree.map(lambda t: t[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    m_new = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    v_new = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_state = dict(state)
    new_state.update({"m": m_new, "v": v_new, "step": step})
    return p_new, new_state, {"grad_norm": gnorm, "lr": lr}
