"""Residual blocks + scan-over-layers stacking with heterogeneous patterns.

A model is ``prefix blocks + N repetitions of a period`` where a *period* is
the minimal repeating list of (mixer_kind, ffn_kind) layer descriptors:

  qwen/mistral/minitron: period = [("attn", "dense")]
  granite-moe:           period = [("attn", "moe")]
  deepseek-v2:           prefix = [("attn", "dense")], period = [("attn", "moe")]
  rwkv6:                 period = [("rwkv", "rwkv_cm")]
  jamba:                 period of 8: mamba x4, attn@idx4, mamba x3,
                         with MoE on odd indices (16e top-2)

Period parameters are stacked on a leading axis and processed with
``jax.lax.scan`` (bounded compile time for 88-layer models); prefix blocks
are unrolled.  Remat (``jax.checkpoint``) wraps the period body in training.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import rwkv6 as R6
from repro.models import scopes
from repro.models import ssm as SSM

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Pattern
# ---------------------------------------------------------------------------


def layer_descriptors(cfg: ModelConfig) -> List[Tuple[str, str]]:
    mixers = cfg.layer_kinds()
    ffns = cfg.ffn_kinds()
    out = []
    for m, f in zip(mixers, ffns):
        if m == "rwkv":
            f = "rwkv_cm"
        out.append((m, f))
    return out


def block_pattern(cfg: ModelConfig) -> Tuple[List, List, int]:
    """Returns (prefix_descriptors, period_descriptors, n_periods)."""
    desc = layer_descriptors(cfg)
    n_prefix = cfg.moe.first_k_dense if cfg.moe else 0
    prefix, rest = desc[:n_prefix], desc[n_prefix:]
    n = len(rest)
    for p in range(1, n + 1):
        if n % p == 0 and rest == rest[:p] * (n // p):
            return prefix, rest[:p], n // p
    return prefix, rest, 1


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, mixer: str, ffn: str) -> Params:
    dt = L.dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_norm(cfg.d_model, cfg.norm, dt)}
    if mixer == "attn":
        p["attn"] = ATT.init_attention(k1, cfg)
    elif mixer == "ssm":
        p["ssm"] = SSM.init_ssm(k1, cfg)
    elif mixer == "rwkv":
        p["rwkv_tm"] = R6.init_time_mix(k1, cfg)
    else:
        raise ValueError(mixer)
    p["norm2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
    if ffn == "dense":
        d_ff = (cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense)
                else cfg.d_ff)
        p["ffn"] = L.init_ffn(k2, cfg.d_model, d_ff, cfg.act, dt)
    elif ffn == "moe":
        p["ffn_moe"] = L.init_moe(k3, cfg, dt)
    elif ffn == "rwkv_cm":
        p["rwkv_cm"] = R6.init_channel_mix(k4, cfg)
    else:
        raise ValueError(ffn)
    return p


def block_cache_spec(cfg: ModelConfig, mixer: str, ffn: str,
                     batch: int, max_len: int) -> Params:
    spec: Params = {}
    if mixer == "attn":
        spec["attn"] = ATT.attention_cache_spec(cfg, batch, max_len)
    elif mixer == "ssm":
        spec["ssm"] = SSM.ssm_cache_spec(cfg, batch)
    elif mixer == "rwkv":
        # includes shift_t (time-mix), shift_c (channel-mix) and wkv state
        spec["rwkv_tm"] = R6.rwkv_cache_spec(cfg, batch)
    return spec


def apply_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                mixer: str, ffn: str, *, mode: str,
                cache: Optional[Params] = None, pos=None,
                causal: bool = True,
                ) -> Tuple[jnp.ndarray, Optional[Params], Dict]:
    """Returns (x, new_cache, ``L.moe_stats``)."""
    cd = L.dtype_of(cfg.compute_dtype)
    stats = L.moe_stats()
    new_cache: Params = {}

    h = L.apply_norm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        y, c = ATT.apply_attention(p["attn"], h, cfg, mode=mode,
                                   cache=None if cache is None else cache["attn"],
                                   pos=pos, causal=causal)
        if c is not None:
            new_cache["attn"] = c
    elif mixer == "ssm":
        with jax.named_scope(scopes.SSM):
            y, c = SSM.apply_ssm(p["ssm"], h, cfg, mode=mode,
                                 cache=None if cache is None else cache["ssm"],
                                 pos=pos)
        if c is not None:
            new_cache["ssm"] = c
    else:  # rwkv time mix
        with jax.named_scope(scopes.RWKV):
            y, c = R6.apply_time_mix(
                p["rwkv_tm"], h, cfg, mode=mode,
                cache=None if cache is None else cache["rwkv_tm"])
        if c is not None:
            new_cache["rwkv_tm"] = c
    x = x + y.astype(x.dtype)

    h = L.apply_norm(p["norm2"], x, cfg.norm_eps)
    with jax.named_scope(scopes.FFN):
        if ffn == "dense":
            y = L.apply_ffn(p["ffn"], h, cfg.act, cd)
        elif ffn == "moe":
            y, stats = L.apply_moe(p["ffn_moe"], h, cfg, compute_dtype=cd)
        else:  # rwkv channel mix
            y, c = R6.apply_channel_mix(
                p["rwkv_cm"], h, cfg, mode=mode,
                cache=None if cache is None else cache["rwkv_tm"])
            if c is not None:
                new_cache.setdefault("rwkv_tm", {}).update(c)
    x = x + y.astype(x.dtype)
    return x, (new_cache if new_cache else None), stats


# ---------------------------------------------------------------------------
# Stack (prefix + scanned periods)
# ---------------------------------------------------------------------------


def init_stack(key, cfg: ModelConfig) -> Params:
    prefix, period, n_periods = block_pattern(cfg)
    kp, ks = jax.random.split(key)
    params: Params = {}
    if prefix:
        pkeys = jax.random.split(kp, len(prefix))
        params["prefix"] = {
            f"blk{i}": init_block(pkeys[i], cfg, m, f)
            for i, (m, f) in enumerate(prefix)
        }

    def init_period(k):
        keys = jax.random.split(k, len(period))
        return {f"sub{j}": init_block(keys[j], cfg, m, f)
                for j, (m, f) in enumerate(period)}

    params["periods"] = jax.vmap(init_period)(jax.random.split(ks, n_periods))
    return params


def stack_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    prefix, period, n_periods = block_pattern(cfg)
    spec: Params = {}
    if prefix:
        spec["prefix"] = {
            f"blk{i}": block_cache_spec(cfg, m, f, batch, max_len)
            for i, (m, f) in enumerate(prefix)
        }
    per = {f"sub{j}": block_cache_spec(cfg, m, f, batch, max_len)
           for j, (m, f) in enumerate(period)}
    spec["periods"] = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype), per)
    return spec


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def apply_stack(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                mode: str, cache: Optional[Params] = None, pos=None,
                causal: bool = True, remat: str = "dots",
                ) -> Tuple[jnp.ndarray, Optional[Params], Dict]:
    """Run prefix blocks then the scanned periods.

    Returns (x, new_cache (same structure as cache, or None), the layers'
    ``L.moe_stats`` merged).
    """
    prefix, period, n_periods = block_pattern(cfg)
    total = L.moe_stats()
    new_cache: Params = {}

    if prefix:
        pc = {}
        for i, (m, f) in enumerate(prefix):
            blk = params["prefix"][f"blk{i}"]
            c_in = None if cache is None else cache["prefix"][f"blk{i}"]
            # in training a prefix block is rematerialised as a period is
            run = _remat_wrap(
                lambda blk, x, c_in, m=m, f=f: apply_block(
                    blk, x, cfg, m, f, mode=mode, cache=c_in, pos=pos,
                    causal=causal),
                remat if mode == "train" else "none")
            x, c, stats = run(blk, x, c_in)
            total = L.merge_moe_stats(total, stats)
            if c is not None:
                pc[f"blk{i}"] = c
        if pc:
            new_cache["prefix"] = pc

    def period_fn(x, scanned):
        p_params, p_cache = scanned
        caches_out = {}
        period_stats = L.moe_stats()
        for j, (m, f) in enumerate(period):
            c_in = None if p_cache is None else p_cache[f"sub{j}"]
            x, c, stats = apply_block(p_params[f"sub{j}"], x, cfg, m, f,
                                      mode=mode, cache=c_in, pos=pos,
                                      causal=causal)
            period_stats = L.merge_moe_stats(period_stats, stats)
            if c is not None:
                caches_out[f"sub{j}"] = c
        return x, (caches_out if caches_out else None, period_stats)

    body = _remat_wrap(period_fn, remat if mode == "train" else "none")
    xs = (params["periods"], cache["periods"] if cache is not None else None)
    x, (period_caches, per) = jax.lax.scan(body, x, xs)
    total = L.merge_moe_stats(total, L.moe_stats(
        jnp.sum(per["aux"]), jnp.sum(per["held"]), jnp.max(per["max_load"])))
    if period_caches is not None:
        new_cache["periods"] = period_caches
    return x, (new_cache if new_cache else None), total
