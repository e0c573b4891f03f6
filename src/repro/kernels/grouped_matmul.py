"""Grouped matrix products for the dropless MoE: rows grouped by expert,
each group times its own expert's matrix.

On a TPU this runs JAX's megablox kernels
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward, ``gmm``
and ``tgmm`` backward), which visit only the row tiles that hold a group,
so the work scales with the rows grouped and not with the rows passed.
Rows past the last group are not written and may hold anything: callers
mask them.  Elsewhere (the CPU tests) it is ``lax.ragged_dot``.  The
kernel is chosen when the program is lowered, as ``layers.attention``
chooses splash.  XLA's own TPU lowering of ``lax.ragged_dot`` is a
kernel too, but its custom call drops the op's name stack, so its time
would fall outside the layer scopes that the benchmark reads.  GSPMD does
not partition a Mosaic call, so on a mesh of more than one device the
products stay ``lax.ragged_dot`` on every platform, as ``layers.attention``
keeps its scan there.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.sharding import active_mesh

# (rows, contraction, output) tiles of the kernel for the expert's input
# projections (d -> f) and its output projection (f -> d), from a sweep of
# the tilings that fit VMEM with float32 output, on one TPU v5e at
# deepseek-v2-lite's shapes (d 2048, f 1408, 8 experts, 49,152 rows).
# Narrower products clamp them to their own k and n.
TILING_IN = (512, 512, 1408)
TILING_OUT = (512, 1408, 512)


def uses_kernel() -> bool:
    """Whether the products may run the megablox kernels on a TPU: on at
    most one device."""
    mesh = active_mesh()
    return mesh is None or mesh.size == 1


def _megablox(lhs, rhs, group_sizes, tiling):
    (m, k), n = lhs.shape, rhs.shape[2]
    tiling = (tiling[0], min(tiling[1], k), min(tiling[2], n))
    pad = -m % tiling[0]          # the kernel takes whole row tiles
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs, group_sizes, jnp.float32, tiling, None,
                       None, False, False)
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, tiling):
    """(m, k) rows, ``group_sizes[g]`` of them in turn times ``rhs[g]``
    (g, k, n); float32 (m, n).  ``tiling`` is the TPU kernel's."""
    ragged = partial(jax.lax.ragged_dot, preferred_element_type=jnp.float32)
    if not uses_kernel():
        return ragged(lhs, rhs, group_sizes)
    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes, tpu=partial(_megablox, tiling=tiling),
        default=ragged)
