"""Jit'd public wrapper for the selective-scan kernel."""
from __future__ import annotations

from repro.kernels import check_backend
from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def ssm_scan_op(u, dt, A, B, C, D, h0, *, interpret: bool = False):
    check_backend(interpret)
    return ssm_scan(u, dt, A, B, C, D, h0, interpret=interpret)


__all__ = ["ssm_scan_op", "ssm_scan", "ssm_scan_ref"]
