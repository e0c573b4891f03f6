"""Device time of the scopes the program nests inside ``ffn``.

The dropless MoE (``repro/models/layers.py`` ``dropless_moe``) marks its
router, top-k, balance loss, sort, permutation and combine ``moe_route``
and the held experts' grouped matmuls and SwiGLU ``moe_experts``, both
inside the ``ffn`` scope, so ``scopes.py`` counts their ops under ``ffn``
(its innermost name of ``SCOPES``).  This module puts each op of the train
step under the innermost of ``NESTED`` in its ``tf_op``, where no name of
``scopes.SCOPES`` lies inside it, from the same metadata
(``scopes.read_metadata``, ``scopes._module_ops``).  A program without
these scopes gives ``None``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import scopes
import trace_reduce

NESTED = ("moe_route", "moe_experts")


def nested_scope_of(tf_op: str) -> Optional[str]:
    """The innermost name of ``NESTED`` in an op's ``tf_op``, unless an
    outer scope's name lies inside it; else ``None``."""
    for w in reversed(scopes._WORD.findall(tf_op or "")):
        if w in NESTED:
            return w
        if w in scopes.SCOPES:
            return None
    return None


@functools.lru_cache(maxsize=4)
def _cached(path: str, stamp: Tuple[int, int],
            module: str) -> Optional[Dict[str, float]]:
    calls, ops_in = scopes._module_ops(trace_reduce.read_xplane(path),
                                       scopes.read_metadata(path), module)
    sec: Dict[str, float] = {}
    for _, m, d in ops_in:
        k = nested_scope_of(str(m.get("tf_op", "")))
        if k:
            sec[k] = sec.get(k, 0.0) + d
    if not calls or not sec:
        return None
    return {k: v / calls for k, v in sec.items()}


def nested_times(path: str,
                 module: str = scopes.TRAIN_MODULE) -> Optional[Dict]:
    """{nested scope: device seconds} per call of ``module``; ``None``
    where no op of it carries one.  Cached by path."""
    return _cached(path, scopes._stamp(path), module)


def train_nested(ctx, scope: str) -> Optional[float]:
    """A reader's view: ``scope``'s device seconds per call of the traced
    train step of the context's cell, or ``None``."""
    path = scopes.cell_trace(ctx.cell.name)
    times = nested_times(path) if path else None
    return None if times is None else times.get(scope, 0.0)
