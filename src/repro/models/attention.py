"""Attention mixers: GQA (with RoPE, optional QKV bias) and MLA (DeepSeek-V2).

Cache layouts (per layer):
  gqa: {"k": (B, Hkv, S_max, hd), "v": (B, Hkv, S_max, hd)}
  mla: {"ckv": (B, S_max, kv_lora), "krope": (B, S_max, rope_dim)}
MLA decode uses matrix absorption (q-side W_uk, out-side W_uv) so decode
attends over the *compressed* latent cache — the technique's entire memory
advantage.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import AttentionConfig, ModelConfig
from repro.models import layers as L
from repro.models import scopes
from repro.sharding import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig) -> Params:
    a = cfg.attention
    dt = L.dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "wq": L.init_linear(k1, d, a.num_heads * a.head_dim, dt, bias=a.qkv_bias),
        "wk": L.init_linear(k2, d, a.num_kv_heads * a.head_dim, dt, bias=a.qkv_bias),
        "wv": L.init_linear(k3, d, a.num_kv_heads * a.head_dim, dt, bias=a.qkv_bias),
        "wo": L.init_linear(k4, a.num_heads * a.head_dim, d, dt),
    }


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    a = cfg.attention
    dt = L.dtype_of(cfg.compute_dtype)
    shp = (batch, a.num_kv_heads, max_len, a.head_dim)
    return {"k": jax.ShapeDtypeStruct(shp, dt), "v": jax.ShapeDtypeStruct(shp, dt)}


@jax.named_scope(scopes.ATTN_PROJ)
def apply_gqa(p: Params, x: jnp.ndarray, cfg: ModelConfig, *, mode: str,
              cache: Optional[Params] = None, pos=None,
              causal: bool = True) -> Tuple[jnp.ndarray, Optional[Params]]:
    """mode: 'train' | 'prefill' | 'decode'.  x: (B, S, D)."""
    a = cfg.attention
    cd = L.dtype_of(cfg.compute_dtype)
    B, S, D = x.shape
    H, Hkv, hd = a.num_heads, a.num_kv_heads, a.head_dim

    q = L.linear(p["wq"], x, cd).reshape(B, S, H, hd)
    k = L.linear(p["wk"], x, cd).reshape(B, S, Hkv, hd)
    v = L.linear(p["wv"], x, cd).reshape(B, S, Hkv, hd)

    if mode == "decode":
        positions = jnp.asarray(pos)[None] if jnp.ndim(pos) == 0 else pos
        positions = jnp.broadcast_to(positions.reshape(-1, 1), (B, S))
    else:
        positions = jnp.arange(S)[None, :]
    q = L.apply_rope(q, positions, a.rope_theta)
    k = L.apply_rope(k, positions, a.rope_theta)
    q = q.transpose(0, 2, 1, 3)     # (B,H,S,hd)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q = constrain(q, ("batch", "heads", "seq", None))

    new_cache = None
    if mode == "decode":
        assert cache is not None
        with jax.named_scope(scopes.KV_WRITE):
            k_c = k.astype(cache["k"].dtype)
            v_c = v.astype(cache["v"].dtype)
            if jnp.ndim(pos) == 0:
                pk = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_c, pos,
                                                         axis=2)
                pv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_c, pos,
                                                         axis=2)
            else:
                # per-slot positions (continuous batching: each slot writes
                # its own cache index) — one update per batch row
                upd = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(
                        c, u, p, axis=1))
                pk = upd(cache["k"], k_c, pos)
                pv = upd(cache["v"], v_c, pos)
        new_cache = {"k": pk, "v": pv}
        pk = constrain(pk, ("batch", "kv_heads", "kv_seq", None))
        pv = constrain(pv, ("batch", "kv_heads", "kv_seq", None))
        kv_len = jnp.broadcast_to(jnp.asarray(pos) + 1, (B,)).astype(jnp.int32)
        with jax.named_scope(scopes.ATTN_CORE):
            out = L.attention(q, pk.astype(cd), pv.astype(cd), causal=False,
                              kv_len=kv_len)
    else:
        with jax.named_scope(scopes.ATTN_CORE):
            out = L.attention(q, k, v, causal=causal)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}

    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    y = L.linear(p["wo"], out, cd)
    return constrain(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig) -> Params:
    a = cfg.attention
    dt = L.dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    qk_dim = a.qk_nope_head_dim + a.qk_rope_head_dim
    p: Params = {}
    if a.q_lora_rank:
        p["wq_a"] = L.init_linear(ks[0], d, a.q_lora_rank, dt)
        p["q_norm"] = L.init_norm(a.q_lora_rank, cfg.norm, dt)
        p["wq_b"] = L.init_linear(ks[1], a.q_lora_rank, a.num_heads * qk_dim, dt)
    else:
        p["wq"] = L.init_linear(ks[0], d, a.num_heads * qk_dim, dt)
    p["wkv_a"] = L.init_linear(ks[2], d, a.kv_lora_rank + a.qk_rope_head_dim, dt)
    p["kv_norm"] = L.init_norm(a.kv_lora_rank, cfg.norm, dt)
    p["wkv_b"] = L.init_linear(
        ks[3], a.kv_lora_rank,
        a.num_heads * (a.qk_nope_head_dim + a.v_head_dim), dt)
    p["wo"] = L.init_linear(ks[4], a.num_heads * a.v_head_dim, d, dt)
    return p


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    a = cfg.attention
    dt = L.dtype_of(cfg.compute_dtype)
    return {
        "ckv": jax.ShapeDtypeStruct((batch, max_len, a.kv_lora_rank), dt),
        "krope": jax.ShapeDtypeStruct((batch, max_len, a.qk_rope_head_dim), dt),
    }


def _mla_q(p: Params, x, a: AttentionConfig, cd) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    qk_dim = a.qk_nope_head_dim + a.qk_rope_head_dim
    if "wq_a" in p:
        ql = L.apply_norm(p["q_norm"], L.linear(p["wq_a"], x, cd))
        q = L.linear(p["wq_b"], ql, cd)
    else:
        q = L.linear(p["wq"], x, cd)
    q = q.reshape(B, S, a.num_heads, qk_dim)
    return q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]


@jax.named_scope(scopes.ATTN_PROJ)
def apply_mla(p: Params, x: jnp.ndarray, cfg: ModelConfig, *, mode: str,
              cache: Optional[Params] = None, pos=None,
              causal: bool = True) -> Tuple[jnp.ndarray, Optional[Params]]:
    a = cfg.attention
    cd = L.dtype_of(cfg.compute_dtype)
    B, S, D = x.shape
    H = a.num_heads
    nope, rope, vdim = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim

    if mode == "decode":
        positions = jnp.broadcast_to(jnp.asarray(pos).reshape(-1, 1), (B, S))
    else:
        positions = jnp.arange(S)[None, :]

    q_nope, q_rope = _mla_q(p, x, a, cd)
    q_rope = L.apply_rope(q_rope, positions, a.rope_theta)

    kv_a = L.linear(p["wkv_a"], x, cd)
    ckv = L.apply_norm(p["kv_norm"], kv_a[..., :a.kv_lora_rank])
    krope = kv_a[..., a.kv_lora_rank:][:, :, None, :]       # (B,S,1,rope)
    krope = L.apply_rope(krope, positions, a.rope_theta)[:, :, 0, :]

    wkv_b = p["wkv_b"]["w"].astype(cd).reshape(a.kv_lora_rank, H, nope + vdim)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]

    scale = 1.0 / math.sqrt(nope + rope)

    if mode == "decode":
        assert cache is not None and S == 1
        ckv_t = ckv.astype(cache["ckv"].dtype)
        krope_t = krope.astype(cache["krope"].dtype)
        with jax.named_scope(scopes.KV_WRITE):
            if jnp.ndim(pos) == 0:
                ckv_c = jax.lax.dynamic_update_slice_in_dim(
                    cache["ckv"], ckv_t, pos, axis=1)
                krope_c = jax.lax.dynamic_update_slice_in_dim(
                    cache["krope"], krope_t, pos, axis=1)
            else:
                # per-slot positions: one latent-cache update per batch row
                upd = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(
                        c, u, p, axis=0))
                ckv_c = upd(cache["ckv"], ckv_t, pos)
                krope_c = upd(cache["krope"], krope_t, pos)
        new_cache = {"ckv": ckv_c, "krope": krope_c}
        ckv_c = constrain(ckv_c, ("batch", "kv_seq", None))
        # --- absorbed decode over the latent cache ---
        # (f32 accumulation via preferred_element_type; never materialise an
        # f32 copy of the compressed cache)
        q_abs = jnp.einsum("bshn,lhn->bhl", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(cd)
        with jax.named_scope(scopes.ATTN_CORE):
            s = jnp.einsum("bhl,btl->bht", q_abs, ckv_c,
                           preferred_element_type=jnp.float32)
            s += jnp.einsum("bshr,btr->bht", q_rope, krope_c,
                            preferred_element_type=jnp.float32)
            s *= scale
            t_pos = jnp.arange(ckv_c.shape[1])
            pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
            mask = t_pos[None, None, :] <= pos_b[:, None, None]
            s = jnp.where(mask, s, -jnp.inf)
            probs = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bht,btl->bhl", probs.astype(cd), ckv_c,
                             preferred_element_type=jnp.float32).astype(cd)
        out = jnp.einsum("bhl,lhv->bhv", ctx, w_uv,
                         preferred_element_type=jnp.float32)
        out = out.reshape(B, 1, H * vdim).astype(cd)
    else:
        # --- expanded prefill/train ---
        k_nope = jnp.einsum("btl,lhn->bthn", ckv, w_uk,
                            preferred_element_type=jnp.float32).astype(cd)
        v = jnp.einsum("btl,lhv->bthv", ckv, w_uv,
                       preferred_element_type=jnp.float32).astype(cd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, rope))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        q = constrain(q, ("batch", "heads", "seq", None))
        with jax.named_scope(scopes.ATTN_CORE):
            out = L.attention(q, k, v, causal=causal)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * vdim)
        new_cache = {"ckv": ckv, "krope": krope} if mode == "prefill" else None

    y = L.linear(p["wo"], out, cd)
    return constrain(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    return init_mla(key, cfg) if cfg.attention.kind == "mla" else init_gqa(key, cfg)


def apply_attention(p, x, cfg, **kw):
    if cfg.attention.kind == "mla":
        return apply_mla(p, x, cfg, **kw)
    return apply_gqa(p, x, cfg, **kw)


def attention_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.attention.kind == "mla":
        return mla_cache_spec(cfg, batch, max_len)
    return gqa_cache_spec(cfg, batch, max_len)
