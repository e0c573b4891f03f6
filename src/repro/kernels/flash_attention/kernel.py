"""Pallas TPU flash-attention (forward) kernel.

Online-softmax attention tiled for VMEM: grid (batch*q_heads, q_blocks,
kv_blocks) with the kv axis sequential ("arbitrary") so the (m, l, acc)
running statistics live in VMEM scratch across kv steps.  GQA is handled by
indexing the kv arrays at ``head // group``.  Block shapes default to
(128, head_dim) — MXU-aligned for head_dim in {64, 128, 192, 256}.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, q_offset: int,
                  block_q: int, block_k: int, kv_len: int):
    _, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                   # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_offset", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Sk, hd).  Returns (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = -(-Sq // block_q), -(-Sk // block_k)
    q_pad, k_pad = nq * block_q - Sq, nk * block_k - Sk
    qf = q.reshape(B * Hq, Sq, hd)
    kf = k.reshape(B * Hkv, Sk, hd)
    vf = v.reshape(B * Hkv, Sk, hd)
    if q_pad:
        qf = jnp.pad(qf, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        kf = jnp.pad(kf, ((0, 0), (0, k_pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, k_pad), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, Hq, Sq, hd)
