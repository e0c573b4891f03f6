"""The numbers that decide ``correct``, and how they are compared.

Training: the program's first three steps against the reference's.

* ``loss``: the largest relative gap between the program's loss and the
  reference's, over the three steps.
* ``grad``: the first gradient as the optimizer gets it (clipped), read
  from the program's Adam state after step 1 (m = (1 - b1) g).  For each
  leaf (each layer's slice of a stacked tensor is a leaf of its own), the
  gap between the program's norm and the reference's, over the larger of
  the reference's norm of that leaf and of the median leaf; the worst leaf.
* ``change``: the same for the parameters' change after three steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's (a key projection's bias, whose gradient is zero under softmax up to
rounding) move under Adam by round-off alone; they are left out of both
``grad`` and ``change``.

Serving: ``gap`` is the widest amount by which a served token's logit lies
below the reference's best logit at that position.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

NOUGHT = 1e-3        # a leaf under this share of the median gradient


def slice_norms(tree) -> Dict[str, np.ndarray]:
    """Norm of every leaf; stacked per-layer leaves give one norm per
    layer.  Jitted per tree structure."""
    paths = [p for p, _ in weights.leaf_paths(tree)]

    @jax.jit
    def f(t):
        out = []
        for (p, x) in weights.leaf_paths(t):
            x = x.astype(jnp.float32)
            if "/periods/" in p:
                out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                    range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x))[None])
        return out
    return dict(zip(paths, (np.asarray(v, np.float64) for v in f(tree))))


def change_norms(params, shapes, seed: int) -> Dict[str, np.ndarray]:
    """Per-leaf norms of (params - the weights ``seed`` made)."""
    make = weights.make_fn(shapes)
    key = weights.key_from_seed(seed)
    delta = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, make(k)))(params, key)
    out = slice_norms(delta)
    del delta
    return out


def flat(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for p, v in norms.items():
        for i, x in enumerate(np.atleast_1d(v)):
            out[f"{p}[{i}]" if len(np.atleast_1d(v)) > 1 else p] = float(x)
    return out


def kept_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= NOUGHT * med]


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> Tuple[float, str]:
    med = float(np.median([ref[k] for k in keep]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [3], "grad": flat norms, "change":
    flat norms}.  Returns the numbers compared and the worst leaves."""
    keep = kept_leaves(ref["grad"])
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    g, gk = worst_gap(prog["grad"], ref["grad"], keep)
    c, ck = worst_gap(prog["change"], ref["change"], keep)
    return {"loss": loss, "grad": g, "change": c, "_grad_leaf": gk,
            "_change_leaf": ck, "_left_out": len(ref["grad"]) - len(keep)}


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) over the limits' numbers."""
    rows = [(k, float(readings[k]), float(limits[k])) for k in sorted(limits)]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
