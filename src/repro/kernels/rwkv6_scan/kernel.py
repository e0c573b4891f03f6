"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked).

Grid (batch*heads, chunks) with the chunk axis sequential: the (hd x hd)
WKV state lives in VMEM scratch across chunks.  Within a chunk the
contribution of in-chunk pairs is a masked (c x c) matmul with per-channel
pairwise decays; every exponent is a difference of cumulative log-decays
inside one chunk (<= 0), so the kernel is overflow-safe by construction —
the same formulation as the XLA twin in repro.models.rwkv6.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                o_ref, sout_ref, state_scr, *, chunk: int):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # (c, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # (c, hd) log-decays (<0)
    u = u_ref[0].astype(jnp.float32)          # (1, hd) bonus

    # inclusive logW as a lower-triangular matmul (Mosaic has no cumsum)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (t_idx >= i_idx).astype(jnp.float32)
    cum = jax.lax.dot(tril, lw, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    cum_ex = cum - lw                         # exclusive logW (W_{t-1})

    # intra-chunk pairwise decays: exp(cum_ex[t] - cum[i]) for i < t
    diff = cum_ex[:, None, :] - cum[None, :, :]          # (t, i, hd)
    decay = jnp.exp(jnp.minimum(diff, 0.0))
    A = jnp.sum(decay * r[:, None, :] * k[None, :, :], axis=-1)   # (t, i)
    A = jnp.where(t_idx > i_idx, A, 0.0)
    out = jax.lax.dot(A, v, preferred_element_type=jnp.float32)

    # bonus (current token) term
    Au = jnp.sum(r * u * k, axis=-1, keepdims=True)      # (c, 1)
    out += Au * v

    # cross-chunk: query the carried state, decayed from chunk start
    s = state_scr[...]                                   # (hd, hd)
    out += jax.lax.dot(r * jnp.exp(cum_ex), s,
                       preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)

    # state update: k decayed from position i to the end of the chunk
    wlast = cum[-1:, :]                                  # (1, hd)
    kdec = k * jnp.exp(wlast - cum)                      # exponent <= 0
    state_scr[...] = s * jnp.exp(wlast.T) + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _finish():
        sout_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, logw, u, state0, *, chunk: int = 32,
               interpret: bool = False):
    """r,k,v,logw: (N, S, hd) with N = batch*heads; u: (N, hd);
    state0: (N, hd, hd) f32.  Returns (out (N,S,hd) f32, state (N,hd,hd) f32).
    """
    N, S, hd = r.shape
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0))
        r = jnp.pad(r, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        logw = jnp.pad(logw, zpad)   # log(1)=0 pad is harmless: k,v are 0

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    out, state = pl.pallas_call(
        kernel,
        grid=(N, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda n, c: (n, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda n, c: (n, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda n, c: (n, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda n, c: (n, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda n, c: (n, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda n, c: (n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda n, c: (n, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda n, c: (n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, nc * chunk, hd), jnp.float32),
            jax.ShapeDtypeStruct((N, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(N, 1, hd), state0)
    return out[:, :S], state
