"""Jit'd public wrappers for flash attention."""
from __future__ import annotations

from repro.kernels import check_backend
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.train import causal_flash_attention


def flash_attention_op(q, k, v, *, causal: bool = True, q_offset: int = 0,
                       interpret: bool = False):
    check_backend(interpret)
    return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                           interpret=interpret)


def causal_flash_attention_op(q, k, v, *, scale=None,
                              interpret: bool = False):
    check_backend(interpret)
    return causal_flash_attention(q, k, v, scale=scale, interpret=interpret)


__all__ = ["flash_attention_op", "flash_attention", "attention_ref",
           "causal_flash_attention_op", "causal_flash_attention"]
