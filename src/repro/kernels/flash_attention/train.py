"""Causal flash attention for training: forward and backward on the TPU.

A thin wrapper around JAX's splash attention kernels
(``jax.experimental.pallas.ops.tpu.splash_attention``): the forward keeps
its (q-block x kv-block) scores and running statistics in VMEM, visits only
the blocks at or below the diagonal of a causal mask, and has its own
backward (one fused kernel for dq, dk and dv).  GQA is native: q head ``h``
reads kv head ``h // (Hq // Hkv)``.  v may have its own head dim, as latent
attention's has (q.k 192, v 128).

Numerics are at least those of ``repro.models.layers.chunked_attention``:
bf16 operands, f32 scores, statistics and accumulation (the forward's p.v
even takes p and v in f32).  Splash takes no softmax scale, so the scale
(``1/sqrt(hd)`` unless given) is folded into q: in q's dtype where it is a
power of two (exact: hd 64), else in f32 and cast back.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# q and kv block of the forward and of the fused backward (faster than a
# separate dq kernel), chosen with the kernel's sequence-minor layout of q, k
# and v (the projections' transposes feed it with fewer copies) by sweeps on
# one TPU v5e at qwen1.5-0.5b's train shape (16 heads of 64 over 4096 tokens);
# at deepseek-v2-lite's (16 heads, q.k 192, v 128, 8192 tokens) no other
# block size or layout swept was faster by more than 1.3%
BLOCK = 1024


# (q.k head dim, v head dim) the kernel is used and tested at: equal 64 and
# 128, and latent attention's 192 (128 + 64 rope) with v 128
HEAD_DIMS = ((64, 64), (128, 128), (192, 128))


def supported(seq: int, head_dim: int, v_head_dim: int) -> bool:
    """Shapes the kernel is used and tested at."""
    return seq % BLOCK == 0 and (head_dim, v_head_dim) in HEAD_DIMS


@functools.lru_cache(maxsize=None)
def _kernel(hq: int, seq: int, interpret: bool):
    """The splash kernel for one (heads, sequence) shape, built once: its
    block masks are computed on the host from the causal mask (kv heads
    and head dims it reads from its arguments)."""
    # imported here: Pallas takes ~1.3 s to import, and only a TPU lowering
    # or the interpreter needs it
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    layout = splash.QKVLayout.SEQ_MINOR
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * hq)
    sizes = splash.BlockSizes(
        block_q=BLOCK, block_kv=BLOCK, block_kv_compute=BLOCK,
        block_q_dkv=BLOCK, block_kv_dkv=BLOCK, block_kv_dkv_compute=BLOCK,
        use_fused_bwd_kernel=True,
        q_layout=layout, k_layout=layout, v_layout=layout)
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mha(mask, block_sizes=sizes,
                                        head_shards=1, q_seq_shards=1,
                                        interpret=interpret)
    # host constants, whatever device or trace the first call came from
    return jax.tree.map(np.asarray, kernel)


def causal_flash_attention(q, k, v, *, scale: Optional[float] = None,
                           interpret: bool = False):
    """q: (B, Hq, S, hd); k: (B, Hkv, S, hd); v: (B, Hkv, S, vd),
    Hq % Hkv == 0.  Causal self-attention from position 0.  ``scale``: the
    scores' factor, 1/sqrt(hd) where None.  Returns (B, Hq, S, vd) in
    q.dtype."""
    _, hq, seq, hd = q.shape
    kernel = _kernel(hq, seq, interpret)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    if math.log2(scale).is_integer():   # exact in q's dtype (hd 64)
        q = q * jnp.asarray(scale, q.dtype)
    else:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return jax.vmap(kernel)(q, k, v).astype(q.dtype)
