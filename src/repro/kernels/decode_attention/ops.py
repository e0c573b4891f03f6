"""Jit'd public wrapper for flash-decode."""
from __future__ import annotations

from repro.kernels import check_backend
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attention_op(q, k, v, kv_len, *, interpret: bool = False):
    check_backend(interpret)
    return decode_attention(q, k, v, kv_len, interpret=interpret)


__all__ = ["decode_attention_op", "decode_attention", "decode_attention_ref"]
