"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

Grid (batch, kv_heads, kv_blocks): the query-head *group* of a GQA kv head
(shape (group, hd)) stays resident in VMEM while kv blocks stream through;
(m, l, acc) accumulate in scratch.  Variable cache occupancy is handled with
a kv_len scalar (positions >= kv_len are masked), so one compiled kernel
serves every decode step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_k: int):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                 # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    mask = k_pos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); k, v: (B, Hkv, S, hd); kv_len: scalar int32.

    Returns (B, Hq, hd): softmax(q k^T / sqrt(hd)) v over positions < kv_len.
    """
    B, Hq, hd = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    block_k = min(block_k, S)
    nk = -(-S // block_k)
    pad = nk * block_k - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(B, Hkv, group, hd)
    kv_len_arr = jnp.asarray(kv_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len_arr, qg, k, v)
    return out.reshape(B, Hq, hd)
