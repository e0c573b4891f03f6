"""Common model building blocks (pure JAX, functional, scan-friendly).

Parameters are plain nested dicts of jnp arrays.  Every init function has a
matching apply function.  Projections are stored as 2-D ``(d_in, d_out)``
matrices (stacked to ``(L, d_in, d_out)`` by the scan-over-layers wrappers),
which keeps the sharding rules uniform (see ``repro.sharding``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import (AttentionConfig, MoEConfig, ModelConfig,
                               RopeScaling)
from repro.kernels import grouped_matmul
from repro.kernels.flash_attention import train as flash_train
from repro.models import scopes
from repro.sharding import active_mesh

Params = Dict[str, Any]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False,
                scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


import os as _os

# Cast matmul outputs to compute dtype BEFORE GSPMD's cross-shard
# partial-sum reduction: keeps the Megatron-TP all-reduce payload in bf16,
# not f32 (2x ICI traffic).  Beyond-paper optimisation; toggle for A/B in
# the perf loop (REPRO_BF16_AR=0 restores the f32-reduce baseline).
CAST_BEFORE_REDUCE = _os.environ.get("REPRO_BF16_AR", "1") != "0"


def linear(p: Params, x: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    # With CAST_BEFORE_REDUCE the dot's *output* dtype is the compute dtype,
    # so GSPMD's cross-shard partial-sum all-reduce runs on bf16 payloads
    # (TPU MXU still accumulates in f32 internally; only the cross-shard
    # reduce is rounded — standard Megatron practice).  A separate
    # cast-after-dot cannot achieve this: GSPMD reduces at the dot output.
    pref = compute_dtype if CAST_BEFORE_REDUCE else jnp.float32
    y = jnp.einsum("...d,df->...f", x.astype(compute_dtype),
                   p["w"].astype(compute_dtype),
                   preferred_element_type=pref)
    if "b" in p:
        y = (y.astype(jnp.float32) + p["b"].astype(jnp.float32))
    return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, head_dim: int, theta: float,
                         max_pos: int) -> float:
    return head_dim * math.log(max_pos / (rotations * 2 * math.pi)) \
        / (2 * math.log(theta))


def yarn_freqs(head_dim: int, theta: float,
               s: RopeScaling) -> Tuple[jnp.ndarray, float]:
    """YaRN's inverse frequencies (hd/2,) and the factor on cos and sin, as
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes them: the
    interpolated frequencies (divided by ``factor``) below the correction
    dim of ``beta_slow``, the original ones above that of ``beta_fast``,
    a linear ramp between."""
    expo = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extra = 1.0 / (theta ** expo)
    inter = 1.0 / (s.factor * theta ** expo)
    dim = partial(_yarn_correction_dim, head_dim=head_dim, theta=theta,
                  max_pos=s.original_max_position_embeddings)
    low = max(math.floor(dim(s.beta_fast)), 0)
    high = min(math.ceil(dim(s.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # share of the original frequency
    mult = yarn_mscale(s.factor, s.mscale) \
        / yarn_mscale(s.factor, s.mscale_all_dim)
    return inter * (1.0 - keep) + extra * keep, mult


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling: Optional[RopeScaling] = None) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    if scaling is None:
        freqs, mult = rope_freqs(hd, theta), 1.0        # (hd/2,)
    else:
        freqs, mult = yarn_freqs(hd, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — online-softmax chunked dot-product attention.
#
# O(S * chunk) live memory instead of O(S^2), numerically identical to full
# softmax attention.  ``attention`` picks the path by backend and shape:
# short sequences materialise the scores (``full_attention``); long causal
# self-attention from position 0 runs the causal Pallas flash kernel with
# its own backward on a TPU (repro/kernels/flash_attention/train.py), at
# the head dims it supports (latent attention's q.k 192 with v 128 among
# them); everything else (decode against a cache, ``kv_len``, ``q_offset``,
# non-causal, other head dims, other backends) runs the scan below.
# ---------------------------------------------------------------------------


def _attn_block(q, k, v, mask, scale):
    """One (q-chunk x kv-chunk) block. q:(B,H,Tq,hd) k,v:(B,H,Tk,hd)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -jnp.inf)
    return s


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      *, causal: bool, q_offset: int = 0,
                      chunk_q: int = 512, chunk_k: int = 1024,
                      kv_len: Optional[jnp.ndarray] = None,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax attention.

    q: (B, Hq, Sq, hd);  k, v: (B, Hkv, Sk, hd) with Hq % Hkv == 0 (GQA).
    ``q_offset``: absolute position of q[0] (for decode / chunked prefill).
    ``kv_len``: optional (B,) actual kv lengths (decode with ragged cache).
    ``scale``: the scores' factor, 1/sqrt(hd) where None.
    Returns (B, Hq, Sq, hd) in q.dtype.
    """
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    vd = v.shape[-1]
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    # broadcast kv heads to q heads (XLA fuses this; no materialised copy
    # thanks to the einsum below operating per kv-head group)
    qg = q.reshape(B, Hkv, group, Sq, hd)

    chunk_q = min(chunk_q, Sq)
    chunk_k = min(chunk_k, Sk)
    nq, nk = -(-Sq // chunk_q), -(-Sk // chunk_k)
    # pad to multiples
    q_pad = nq * chunk_q - Sq
    k_pad = nk * chunk_k - Sk
    if q_pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))

    q_pos = q_offset + jnp.arange(nq * chunk_q)
    k_pos = jnp.arange(nk * chunk_k)
    kv_valid_len = Sk if kv_len is None else kv_len  # scalar or (B,)

    @jax.checkpoint
    def kv_step(carry, kc):
        # remat: never save the (.., Sq, chunk_k) score/probability blocks —
        # that would reconstitute the full S^2 attention matrix in HBM.
        acc, m, denom = carry      # acc:(B,Hkv,g,Sq',hd) m,denom:(B,Hkv,g,Sq',1)
        ks = jax.lax.dynamic_slice_in_dim(k, kc * chunk_k, chunk_k, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(v, kc * chunk_k, chunk_k, axis=2)
        kp = jax.lax.dynamic_slice_in_dim(k_pos, kc * chunk_k, chunk_k, axis=0)
        # f32 accumulation WITHOUT materialising f32 operand copies
        s = jnp.einsum("bngqd,bnkd->bngqk", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones(s.shape, dtype=bool)
        if causal:
            mask = q_pos[None, None, None, :, None] >= kp[None, None, None, None, :]
        if kv_len is not None:
            vl = jnp.asarray(kv_valid_len).reshape(B, 1, 1, 1, 1)
            mask = mask & (kp[None, None, None, None, :] < vl)
        elif k_pad:
            mask = mask & (kp[None, None, None, None, :] < Sk)
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # guard rows where everything is masked (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        denom_new = denom * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bngqk,bnkd->bngqd", p.astype(vs.dtype), vs,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr + pv
        return (acc_new, m_new, denom_new), None

    acc0 = jnp.zeros((B, Hkv, group, nq * chunk_q, vd), jnp.float32)
    m0 = jnp.full((B, Hkv, group, nq * chunk_q, 1), -jnp.inf, jnp.float32)
    d0 = jnp.zeros((B, Hkv, group, nq * chunk_q, 1), jnp.float32)
    (acc, m, denom), _ = jax.lax.scan(kv_step, (acc0, m0, d0),
                                      jnp.arange(nk))
    out = acc / jnp.maximum(denom, 1e-30)
    out = out.reshape(B, Hq, nq * chunk_q, vd)[:, :, :Sq]
    return out.astype(q.dtype)


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                   kv_len=None, scale: Optional[float] = None) -> jnp.ndarray:
    """Reference full-materialisation attention (small shapes only)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    vd = v.shape[-1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, Sq, hd)
    s = jnp.einsum("bngqd,bnkd->bngqk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd) if scale is None else s * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
    mask = mask[None, None, None]
    if kv_len is not None:
        vl = jnp.asarray(kv_len).reshape(B, 1, 1, 1, 1)
        mask = mask & (k_pos[None, None, None, None, :] < vl)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bngqk,bnkd->bngqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, Sq, vd).astype(q.dtype)


def uses_flash_kernel(q, k, v, *, causal: bool, q_offset: int = 0,
                      kv_len=None, scale: Optional[float] = None) -> bool:
    """Whether ``attention`` runs the causal flash kernel on a TPU: causal
    bf16 self-attention from position 0 over the whole sequence, at q.k and
    v head dims the kernel supports (any ``scale``: it is folded into q),
    on at most one device (a Mosaic kernel is not partitioned across a
    mesh)."""
    (_, hq, sq, hd), (_, hkv, sk, _) = q.shape, k.shape
    mesh = active_mesh()
    return (causal and isinstance(q_offset, int) and q_offset == 0
            and kv_len is None and sq == sk
            and hq % hkv == 0 and flash_train.supported(sq, hd, v.shape[-1])
            and all(x.dtype == jnp.bfloat16 for x in (q, k, v))
            and (mesh is None or mesh.size == 1))


def attention(q, k, v, *, causal: bool, q_offset: int = 0, kv_len=None,
              scale: Optional[float] = None,
              chunked_threshold: int = 1024) -> jnp.ndarray:
    """Dispatch: full softmax for short sequences; for long ones the causal
    flash kernel where ``uses_flash_kernel`` and the program is lowered for
    a TPU, else online-softmax.  ``scale``: the scores' factor, 1/sqrt(hd)
    where None."""
    if q.shape[2] * k.shape[2] <= chunked_threshold ** 2:
        return full_attention(q, k, v, causal=causal, q_offset=q_offset,
                              kv_len=kv_len, scale=scale)
    scan = partial(chunked_attention, causal=causal, q_offset=q_offset,
                   kv_len=kv_len, scale=scale)
    if uses_flash_kernel(q, k, v, causal=causal, q_offset=q_offset,
                         kv_len=kv_len, scale=scale):
        # the branch is chosen when the program is lowered for its platform
        return jax.lax.platform_dependent(
            q, k, v, default=scan,
            tpu=partial(flash_train.causal_flash_attention, scale=scale))
    return scan(q, k, v)


# ---------------------------------------------------------------------------
# Feed-forward
# ---------------------------------------------------------------------------


def init_ffn(key, d_model: int, d_ff: int, act: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": init_linear(k1, d_model, d_ff, dtype),
         "w_down": init_linear(k2, d_ff, d_model, dtype)}
    if act == "swiglu":
        p["w_gate"] = init_linear(k3, d_model, d_ff, dtype)
    return p


def apply_ffn(p: Params, x: jnp.ndarray, act: str,
              compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    h = linear(p["w_up"], x, compute_dtype)
    if act == "swiglu":
        g = linear(p["w_gate"], x, compute_dtype)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * h
    elif act == "gelu":
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(compute_dtype)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(compute_dtype)
    else:
        raise ValueError(act)
    return linear(p["w_down"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts.
#
# Two paths.  With a capacity factor (> 0): grouped, capacity-based,
# einsum dispatch/combine; the (group, seq, expert, capacity) dispatch
# tensors reshard under GSPMD into all-to-alls when experts live on the
# "model" mesh axis (expert parallelism, see repro.sharding), and tokens
# over capacity contribute zero (Switch/T5X).  Dropless (<= 0): the
# assignments are sorted by expert and the experts held here
# (``MoEConfig.experts_held``) run grouped matmuls over their own rows
# only, so the work scales with the rows routed to them.
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    keys = jax.random.split(key, 5)
    d, f, e = cfg.d_model, m.d_ff_expert, m.held
    scale_in = 1.0 / math.sqrt(d)
    p = {
        "router": init_linear(keys[0], d, m.num_experts, jnp.float32,
                              scale=scale_in),
        "w_up": (jax.random.normal(keys[1], (e, d, f)) * scale_in).astype(dtype),
        "w_gate": (jax.random.normal(keys[2], (e, d, f)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(keys[3], (e, f, d)) / math.sqrt(f)).astype(dtype),
    }
    if m.num_shared_experts:
        f_sh = m.d_ff_shared or f * m.num_shared_experts
        p["shared"] = init_ffn(keys[4], d, f_sh, "swiglu", dtype)
    if m.scoring == "sigmoid":
        # the correction bias: set by the step (``rebalance_router_bias``),
        # kept in float32 whatever the parameters' dtype; AdamW leaves it
        # as it is, as it gets no gradient and no decay (``/bias``)
        p["router"]["bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    return p


def moe_capacity(seq: int, num_experts: int, top_k: int,
                 capacity_factor: float = 1.25) -> int:
    c = int(math.ceil(seq * top_k / num_experts * capacity_factor))
    return max(4, min(c, seq * top_k))


MOE_GROUP_SIZE = 4096   # routing-group tokens; capacity scales with the
#                         group, NOT the sequence — otherwise the one-hot
#                         dispatch einsum cost grows as S^2 (32k prefill
#                         made dispatch 10-50x the expert FLOPs)


def moe_stats(aux=0.0, held=0.0, max_load=0.0,
              fallback=None) -> Dict[str, jnp.ndarray]:
    """What an FFN reports besides its output: the balance loss, the
    assignments routed to the experts held, and the largest held expert's
    assignments over the mean held expert's (0 for a dense FFN).  A
    dropless layer with a static row bound also counts ``fallback``: 1
    where its held rows passed the bound and it ran over all S*K rows."""
    f32 = jnp.float32
    stats = {"aux": jnp.asarray(aux, f32), "held": jnp.asarray(held, f32),
             "max_load": jnp.asarray(max_load, f32)}
    if fallback is not None:
        stats["fallback"] = jnp.asarray(fallback, f32)
    return stats


def merge_moe_stats(a, b):
    """Over layers: losses, assignments and fallbacks add, the worst load
    is kept (a layer's ``loads`` over all experts, where it counts them,
    stay out)."""
    out = {"aux": a["aux"] + b["aux"], "held": a["held"] + b["held"],
           "max_load": jnp.maximum(a["max_load"], b["max_load"])}
    if "fallback" in a or "fallback" in b:
        out["fallback"] = a.get("fallback", 0.0) + b.get("fallback", 0.0)
    return out


def _load_stats(aux, counts, fallback=None):
    """``moe_stats`` from the assignments of each expert held."""
    counts = counts.astype(jnp.float32)
    return moe_stats(aux, jnp.sum(counts),
                     jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9),
                     fallback)


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              capacity_factor: Optional[float] = None,
              compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict]:
    """x: (G, S, D) groups of tokens. Returns (out, ``moe_stats``)."""
    m = cfg.moe
    cf = m.capacity_factor if capacity_factor is None else capacity_factor
    if cf <= 0:
        y, stats = dropless_moe(p, x, m, compute_dtype)
    else:
        y, stats = _capacity_moe(p, x, m, cf, compute_dtype)
    if "shared" in p:
        y = y + apply_ffn(p["shared"], x, "swiglu", compute_dtype)
    return y, stats


def _capacity_moe(p: Params, x: jnp.ndarray, m: MoEConfig, cf: float,
                  compute_dtype):
    if m.held != m.num_experts or not m.norm_topk_prob \
            or m.scoring != "softmax" or m.routed_scaling_factor != 1.0:
        raise ValueError("experts_held, norm_topk_prob=False, sigmoid "
                         "scoring and a routed scaling factor need the "
                         "dropless MoE (capacity_factor <= 0)")
    G0, S0, D = x.shape
    # re-group long sequences into fixed-size routing groups
    if S0 > MOE_GROUP_SIZE and S0 % MOE_GROUP_SIZE == 0:
        x = x.reshape(G0 * (S0 // MOE_GROUP_SIZE), MOE_GROUP_SIZE, D)
    G, S, D = x.shape
    E, K = m.num_experts, m.num_experts_per_tok
    C = moe_capacity(S, E, K, cf)

    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (G,S,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                 # (G,S,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # expert one-hot per choice: (G,S,K,E)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each (token, choice) within its expert queue
    # priority: earlier tokens first, then earlier choices
    flat = onehot.reshape(G, S * K, E)
    pos = jnp.cumsum(flat, axis=1) * flat - 1.0                   # (G,S*K,E)
    pos = pos.reshape(G, S, K, E)
    within_cap = (pos >= 0) & (pos < C)
    pos = jnp.clip(pos, 0, C - 1).astype(jnp.int32)

    # dispatch one-hot over capacity: (G,S,K,E,C) -> reduce over K
    cap_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * \
        within_cap[..., None] * onehot[..., None]
    dispatch = jnp.sum(cap_oh, axis=2)                            # (G,S,E,C)
    combine = jnp.sum(cap_oh * gate_vals[..., None, None], axis=2)

    from repro.sharding import constrain  # local import avoids cycle

    dispatch = constrain(dispatch, ("batch", None, "expert", None))
    combine = constrain(combine, ("batch", None, "expert", None))
    # expert parallelism: the (E, G, C, *) tensors live expert-sharded on the
    # model axis; GSPMD inserts the dispatch/combine all-to-alls here.
    xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(compute_dtype),
                    x.astype(compute_dtype),
                    preferred_element_type=jnp.float32).astype(compute_dtype)
    xe = constrain(xe, ("expert", "batch", None, None))
    up = jnp.einsum("egcd,edf->egcf", xe, p["w_up"].astype(compute_dtype),
                    preferred_element_type=jnp.float32)
    gate = jnp.einsum("egcd,edf->egcf", xe, p["w_gate"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(compute_dtype)
    h = constrain(h, ("expert", "batch", None, None))
    ye = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(compute_dtype),
                    preferred_element_type=jnp.float32).astype(compute_dtype)
    ye = constrain(ye, ("expert", "batch", None, None))
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(compute_dtype), ye,
                   preferred_element_type=jnp.float32).astype(compute_dtype)
    if (G, S) != (G0, S0):
        y = y.reshape(G0, S0, D)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    density = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))      # (E,)
    router_prob = jnp.mean(probs, axis=(0, 1))                    # (E,)
    aux = E * jnp.sum(density / K * router_prob)
    return y, _load_stats(aux, jnp.sum(onehot, axis=(0, 1, 2)))


# The assignments of T tokens to K experts each, as n = T * K rows sorted by
# expert: row r holds token order[r] // K, and assignment a sits at row
# inverse[a].  Dispatch and combine are each other's transpose; both are
# gathers, so neither pass scatters.  Over the first R rows alone (the held
# rows, where they fit), ``first`` = order[:R], and choice j of token t
# sits at row slot[t, j], or at R where it is not among them: the combine
# reads zero there.


def _dispatch_rows(x, order, inverse, k: int):
    """(T, D) tokens -> (n, D) rows in expert order."""
    return jnp.take(x, order // k, axis=0)


def _combine_rows(y, order, inverse, k: int):
    """(n, D) rows -> (T, D): each token's K rows summed in float32."""
    t = y.shape[0] // k
    return jnp.sum(jnp.take(y, inverse, axis=0).reshape(t, k, -1)
                   .astype(jnp.float32), axis=1)


def _combine_first(y, first, slot, k: int):
    """(R, D) rows -> (T, D): each token's K slots summed in float32, a
    slot past the rows adding zero; the same adds as ``_combine_rows``,
    one (T, D) gather a choice, which XLA fuses into the sum, so that no
    (T, K, D) array is written."""
    return sum(jnp.take(y, slot[:, j], axis=0, fill_value=0)
               .astype(jnp.float32) for j in range(k))


def _rows_vjp(fwd, bwd):
    f = jax.custom_vjp(fwd, nondiff_argnums=(3,))
    # the residual's empty array carries x's dtype for the cotangent
    f.defvjp(lambda x, o, i, k: (fwd(x, o, i, k), (o, i, x[:0])),
             lambda k, res, g: (bwd(g, res[0], res[1], k).astype(
                 res[2].dtype), None, None))
    return f


dispatch_rows = _rows_vjp(_dispatch_rows, _combine_rows)
combine_rows = _rows_vjp(_combine_rows, _dispatch_rows)
dispatch_first = _rows_vjp(_dispatch_rows, _combine_first)
combine_first = _rows_vjp(_combine_first, _dispatch_rows)


def _grouped_swiglu(p: Params, rows, group_sizes, cd):
    """SwiGLU of each held expert over its rows, which ``group_sizes``
    count off in order; rows past the last group are not computed (and
    may hold anything)."""
    mm = partial(grouped_matmul.grouped_matmul, group_sizes=group_sizes)
    gate = mm(rows, p["w_gate"].astype(cd), tiling=grouped_matmul.TILING_IN)
    up = mm(rows, p["w_up"].astype(cd), tiling=grouped_matmul.TILING_IN)
    h = (jax.nn.silu(gate) * up).astype(cd)
    return mm(h, p["w_down"].astype(cd), tiling=grouped_matmul.TILING_OUT)


def dropless_moe(p: Params, x: jnp.ndarray, m: MoEConfig, cd):
    """Top-k MoE that drops nothing, over the experts held (no shared
    expert).

    The router keeps all ``num_experts`` outputs.  Softmax scoring: greedy
    top-k of the softmax.  Sigmoid scoring (DeepSeek-V3's noaux_tc): f32
    sigmoid affinities, top-k of affinity + the router's correction bias
    (no gradient reaches it), gates from the affinities.  Gates are then
    renormalised where ``norm_topk_prob`` and scaled by
    ``routed_scaling_factor``.  The (token, choice)
    assignments are sorted by expert; those of the experts held,
    ``[0, experts_held)``, come first, and grouped matmuls run over them
    alone, or, where ``grouped_rows_floor`` asks for more rows, on over
    the zero rows after them (which change no number).  Where that floor
    lies below half the S*K rows (``held_row_bound``), every row-wide step
    runs over that half alone while the held rows fit it, and over all
    rows, with the same numbers, where they do not (the stats'
    ``fallback`` counts it).  The weighted results
    go back to their tokens (the other experts' part is left out, as on
    the chip that holds only these).  The balance loss is DeepSeek's
    sequence-wise one, E/(S K) * sum_e count_e * mean_s p_e, averaged over
    the sequences of x (B, S, D), where p is the softmax or, under sigmoid
    scoring, the affinities normalised over all experts; count_e counts
    the assignments as routed.  Under sigmoid scoring the stats also hold
    ``loads``, the step's assignments to every one of the E experts, at
    the bias's place in ``p`` (``{"router": {"bias": loads}}``)."""
    B, S, D = x.shape
    E, K, H = m.num_experts, m.num_experts_per_tok, m.held
    n = B * S * K
    sigmoid = m.scoring == "sigmoid"
    with jax.named_scope(scopes.MOE_ROUTE):
        rd = dtype_of(m.router_dtype)
        logits = jnp.einsum("bsd,de->bse", x.astype(rd),
                            p["router"]["w"].astype(rd),
                            preferred_element_type=jnp.float32)
        if sigmoid:
            scores = jax.nn.sigmoid(logits)                       # (B,S,E)
            bias = jax.lax.stop_gradient(p["router"]["bias"])
            _, idx = jax.lax.top_k(scores + bias, K)
            gate = jnp.take_along_axis(scores, idx, axis=-1)      # (B,S,K)
            probs = scores / jnp.sum(scores, -1, keepdims=True)
        else:
            probs = jax.nn.softmax(logits, axis=-1)               # (B,S,E)
            gate, idx = jax.lax.top_k(probs, K)                   # (B,S,K)
        if m.norm_topk_prob:
            gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
        if m.routed_scaling_factor != 1.0:
            gate = gate * m.routed_scaling_factor
        seq_counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                             axis=(1, 2))                          # (B,E)
        aux = jnp.mean(jnp.sum(seq_counts * jnp.mean(probs, axis=1), -1)) \
            * E / (S * K)
        counts = jnp.sum(seq_counts, axis=0)[:H]
        order = jnp.argsort(idx.reshape(n), stable=True)
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    floor, bound = floor_rows(m, n), held_row_bound(m, n)
    if bound is None:
        y = _all_rows(experts, x, gate, order, counts, K, floor, cd)
        fallback = None
    else:
        with jax.named_scope(scopes.MOE_EXPERTS):
            # the weights go in cast, so that their gradients leave the
            # branch in the compute dtype, as the kernels give them
            experts = {k: w.astype(cd) for k, w in experts.items()}
        with jax.named_scope(scopes.MOE_ROUTE):
            y = _bounded_rows(experts, x, gate, order, counts, K, bound,
                              floor, cd)
        fallback = (jnp.sum(counts) > bound).astype(jnp.float32)
    stats = _load_stats(aux, counts, fallback)
    if sigmoid:
        stats["loads"] = {"router": {"bias": jnp.sum(seq_counts, axis=0)}}
    return y, stats


def floor_rows(m: MoEConfig, n: int) -> int:
    """The rows the grouped matmuls visit at least, of a layer's n = S*K:
    ``grouped_rows_floor`` times the rows its held experts expect, where
    they are a share of the experts; else 0."""
    if m.grouped_rows_floor > 0 and m.held < m.num_experts:
        return min(n, math.ceil(m.grouped_rows_floor * n * m.held
                                / m.num_experts))
    return 0


def held_row_bound(m: MoEConfig, n: int) -> Optional[int]:
    """The static bound on a layer's held rows, half its n = S*K rows,
    where the grouped matmuls' floor (``floor_rows``) lies below it; else
    ``None``.  The held rows grow and shrink with the step's routing, and
    a layer whose held rows pass the bound costs what it costs without
    one, at most twice what the bound's rows cost."""
    floor = floor_rows(m, n)
    return n // 2 if 0 < floor < n // 2 else None


def _branches(k: int, bound: int, floor: int, cd):
    return (partial(_first_rows, k=k, bound=bound, floor=floor, cd=cd),
            partial(_all_rows, k=k, floor=floor, cd=cd))


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _bounded_rows(p, x, gate, order, counts, k: int, bound: int, floor: int,
                  cd):
    """The rows of the experts held come first: where they fit ``bound``,
    ``_first_rows``, all of whose row-wide work stops there; else
    ``_all_rows``.  Both ways the backward reruns the branch the forward
    took (which the layer's remat runs anyway), so that neither branch
    keeps the other's residuals."""
    return jax.lax.cond(jnp.sum(counts) <= bound,
                        *_branches(k, bound, floor, cd),
                        p, x, gate, order, counts)


def _bounded_rows_fwd(p, x, gate, order, counts, k, bound, floor, cd):
    return (_bounded_rows(p, x, gate, order, counts, k, bound, floor, cd),
            (p, x, gate, order, counts))


def _bounded_rows_bwd(k, bound, floor, cd, res, g):
    p, x, gate, order, counts = res

    def back(fn):
        return lambda p, x, gate: jax.vjp(
            lambda p, x, gate: fn(p, x, gate, order, counts), p, x, gate)[1](g)
    # the barrier keeps XLA from moving the gradients' consumers (their
    # float32 casts into the stacked gradients) into the branches
    return jax.lax.optimization_barrier(jax.lax.cond(
        jnp.sum(counts) <= bound,
        *map(back, _branches(k, bound, floor, cd)),
        p, x, gate)) + (None, None)


_bounded_rows.defvjp(_bounded_rows_fwd, _bounded_rows_bwd)


def _all_rows(p, x, gate, order, counts, k: int, floor: int, cd):
    """The held experts over all n rows in expert order, the rows past the
    held ones zero; the grouped matmuls run on to ``floor`` rows."""
    B, S, D = x.shape
    n = order.shape[0]
    with jax.named_scope(scopes.MOE_ROUTE):
        inverse = jnp.argsort(order)
        rows = dispatch_rows(x.astype(cd).reshape(B * S, D), order, inverse,
                             k)
        held = (jnp.arange(n) < jnp.sum(counts))[:, None]
        rows = jnp.where(held, rows, 0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        sizes = counts.astype(jnp.int32)
        if floor:
            # the last group runs on over zero rows up to the floor
            sizes = sizes.at[-1].add(jnp.maximum(floor - jnp.sum(sizes), 0))
        out = _grouped_swiglu(p, rows, sizes, cd)
    with jax.named_scope(scopes.MOE_ROUTE):
        w = jnp.take(gate.reshape(n), order)[:, None]
        out = (jnp.where(held, out, 0) * w).astype(cd)
        y = combine_rows(out, order, inverse, k).reshape(B, S, D).astype(cd)
    return y


def _first_rows(p, x, gate, order, counts, k: int, bound: int, floor: int,
                cd):
    """``_all_rows`` over the first ``bound`` rows alone, which hold every
    held row: the same numbers, with no row-wide work past the bound.  The
    grouped matmuls, as there, stop at the held rows or the ``floor``,
    whichever is more."""
    B, S, D = x.shape
    n = order.shape[0]
    with jax.named_scope(scopes.MOE_ROUTE):
        first = order[:bound]
        held = jnp.arange(bound) < jnp.sum(counts)
        slot = jnp.full((B * S, k), bound, jnp.int32).at[
            first // k, first % k].set(
            jnp.where(held, jnp.arange(bound, dtype=jnp.int32), bound),
            unique_indices=True)
        rows = dispatch_first(x.astype(cd).reshape(B * S, D), first, slot, k)
        rows = jnp.where(held[:, None], rows, 0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        sizes = counts.astype(jnp.int32)
        sizes = sizes.at[-1].add(jnp.maximum(floor - jnp.sum(sizes), 0))
        out = _grouped_swiglu(p, rows, sizes, cd)
    with jax.named_scope(scopes.MOE_ROUTE):
        w = jnp.take(gate.reshape(n), first)[:, None]
        out = (jnp.where(held[:, None], out, 0) * w).astype(cd)
        y = combine_first(out, first, slot, k).reshape(B, S, D).astype(cd)
    return y


def rebalance_router_bias(params: Params, loads: Params,
                          rate: float) -> Tuple[Params, jnp.ndarray]:
    """DeepSeek-V3's balancing without an auxiliary loss, once a step:
    each sigmoid router's correction bias moves towards balance,
    b <- b + rate * sign(mean load - load), from the step's loads.
    ``loads`` follows ``params`` down to each bias and holds there its
    (..., E) assignments per expert; the mean is over the E experts.
    Returns (params, the largest |b| after the move)."""
    moved = []

    def move(p, load):
        if isinstance(load, dict):
            return {**p, **{k: move(p[k], v) for k, v in load.items()}}
        b = p + rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
        moved.append(jnp.max(jnp.abs(b)))
        return b

    return move(params, loads), jnp.max(jnp.stack(moved))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed(p: Params, tokens: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0).astype(compute_dtype)

