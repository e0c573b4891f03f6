"""Jit'd public wrapper for the RWKV-6 WKV kernel."""
from __future__ import annotations

from repro.kernels import check_backend
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


def rwkv6_scan_op(r, k, v, logw, u, state0, *, interpret: bool = False):
    check_backend(interpret)
    return rwkv6_scan(r, k, v, logw, u, state0, interpret=interpret)


__all__ = ["rwkv6_scan_op", "rwkv6_scan", "rwkv6_scan_ref"]
