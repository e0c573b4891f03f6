"""Pallas TPU kernel for the Mamba selective scan (chunked).

Grid (batch, d_inner_blocks, chunks) with the chunk axis sequential: the
hidden state is carried in VMEM scratch as (d_state x di_block), so that
d_inner lies along the 128-wide lanes and the small d_state along the
sublanes.  Within a chunk the recurrence h_t = da_t * h_{t-1} + dbu_t runs
as a ``fori_loop`` over the chunk rows — the same math as the XLA twin in
repro.models.ssm.selective_scan_chunked.  Blocking over d_inner keeps every
block inside VMEM for d_inner up to 16384 (jamba).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hout_ref, h_scr, *, chunk: int):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    neg_a = -jnp.exp(at_ref[...].astype(jnp.float32))   # (ds, dib)
    D = d_ref[...].astype(jnp.float32)                   # (1, dib)

    def row(t, h):                                       # h: (ds, dib)
        u = u_ref[0, pl.ds(t, 1), :].astype(jnp.float32)     # (1, dib)
        dt = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1, dib)
        B = b_ref[0, t].astype(jnp.float32)                  # (ds, 1)
        C = c_ref[0, t].astype(jnp.float32)                  # (ds, 1)
        h = jnp.exp(dt * neg_a) * h + B * (dt * u)
        y = jnp.sum(C * h, axis=0, keepdims=True) + u * D
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, row, h_scr[...])

    @pl.when(c_idx == nc - 1)
    def _finish():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_di", "interpret"))
def ssm_scan(u, dt, A, B, C, D, h0, *, chunk: int = 64,
             block_di: int = 512, interpret: bool = False):
    """u, dt: (Bz, S, di); A: (di, ds); B, C: (Bz, S, ds); D: (di,);
    h0: (Bz, di, ds) f32.  Returns (y (Bz,S,di) f32, h (Bz,di,ds) f32)."""
    Bz, S, di = u.shape
    ds = A.shape[-1]
    chunk = min(chunk, S)
    block_di = min(block_di, di)
    nc = -(-S // chunk)
    ndi = -(-di // block_di)
    assert di % block_di == 0, "d_inner must divide block_di"
    pad = nc * chunk - S
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    # d_state goes to the sublanes: B, C as per-row (ds, 1) columns, A and
    # the state as (ds, di)
    B4, C4 = B[..., None], C[..., None]
    At = A.T
    h0t = jnp.swapaxes(h0, 1, 2)
    D2 = D.reshape(1, di)

    y, ht = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(Bz, ndi, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((ds, block_di), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, chunk, ds, 1), lambda b, d, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, ds, 1), lambda b, d, c: (b, c, 0, 0)),
            pl.BlockSpec((1, block_di), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, ds, block_di), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ds, block_di), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bz, nc * chunk, di), jnp.float32),
            jax.ShapeDtypeStruct((Bz, ds, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, block_di), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, At, B4, C4, D2, h0t)
    return y[:, :S], jnp.swapaxes(ht, 1, 2)
