"""Seeded weights in the program's parameter layout, made on the device.

The benchmark makes the weights itself, so that the program under test and
the plain reference start from the same numbers and neither made them.  The
layout (which leaves, their shapes and dtypes) is read from the program's
``param_shapes``; the values come from one jitted call on ``--seed``:

* matrices ``w`` and MoE expert tensors: normal / sqrt(fan_in);
* norm scales: 1 + 0.1 * normal, biases: 0.2 * normal (so that both are
  exercised, where zeros and ones would hide them);
* the embedding table: 0.02 * normal.
"""
from __future__ import annotations

import math

import jax


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits allowed)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_paths(tree, prefix=""):
    """[(path, leaf)] in ``jax.tree.leaves`` order for nested dicts."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(leaf_paths(tree[k], f"{prefix}/{k}"))
        return out
    return [(prefix, tree)]


def _draw(key, path: str, shape, dtype):
    if path.endswith("/scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape)
    elif path.endswith("/b") or path.endswith("/bias"):
        x = 0.2 * jax.random.normal(key, shape)
    elif path.endswith("/table"):
        x = 0.02 * jax.random.normal(key, shape)
    else:
        x = jax.random.normal(key, shape) / math.sqrt(shape[-2])
    return x.astype(dtype)


def _unflatten(shapes, values):
    it = iter(values)

    def build(tree):
        if isinstance(tree, dict):
            return {k: build(tree[k]) for k in sorted(tree)}
        return next(it)
    return build(shapes)


def make_fn(shapes):
    """``f(seed_key) -> params`` for the given ShapeDtypeStruct tree."""
    paths = leaf_paths(shapes)

    def make(key):
        vals = [_draw(jax.random.fold_in(key, i), p, s.shape, s.dtype)
                for i, (p, s) in enumerate(paths)]
        return _unflatten(shapes, vals)
    return make


def make_params(shapes, seed: int, out_shardings=None):
    """The weights for ``seed`` in one jitted call, on the device."""
    fn = jax.jit(make_fn(shapes), out_shardings=out_shardings)
    return fn(key_from_seed(seed))


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)
