"""Sharding rules: logical activation/parameter axes -> mesh axes.

Model code annotates activations with *logical* axis names via
:func:`constrain`; launchers install a rule set for the active mesh.  Rules
degrade gracefully: an axis whose size does not divide its mesh axis falls
back to replication (required because e.g. qwen2.5-14b has 40 heads on a
16-way model axis, and granite's vocab 49155 is odd).

Parameter sharding is name/shape based (:func:`param_pspecs`): 2-D matrices
are FSDP-sharded on d_in ("data") and tensor-parallel on d_out ("model")
when divisible; expert tensors put the expert dim on "model" (expert
parallelism shares the model axis); embeddings shard vocab on "model" and
d_model on "data".
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# logical name -> preferred mesh axes (first that divides wins; tuples mean
# use the product of axes jointly, e.g. batch over (pod, data)).
DEFAULT_RULES: Dict[str, Tuple] = {
    "batch": (("pod", "data"), ("data",)),
    "seq": (("model",),),          # sequence parallelism (long-context)
    "embed": (("model",),),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "mlp": (("model",),),
    "vocab": (("model",),),
    "expert": (("model",),),
    "kv_seq": (("model",),),       # decode KV-cache sequence dim
    "none": ((),),
}

_ACTIVE: Dict[str, Any] = {"mesh": None, "rules": DEFAULT_RULES,
                           "seq_parallel": False}


@contextmanager
def activation_rules(mesh: Optional[Mesh], rules: Optional[Dict] = None,
                     seq_parallel: bool = False):
    prev = dict(_ACTIVE)
    _ACTIVE["mesh"] = mesh
    _ACTIVE["rules"] = rules or DEFAULT_RULES
    _ACTIVE["seq_parallel"] = seq_parallel
    try:
        yield
    finally:
        _ACTIVE.update(prev)


def active_mesh() -> Optional[Mesh]:
    """The mesh installed by :func:`activation_rules`, if any."""
    return _ACTIVE["mesh"]


def _mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _resolve_axis(logical: Optional[str], dim_size: int,
                  sizes: Dict[str, int], used: set,
                  strict: bool = False) -> Optional[Any]:
    if logical is None or logical == "none":
        return None
    for cand in _ACTIVE["rules"].get(logical, ((),)):
        axes = [a for a in cand if a in sizes and a not in used]
        if not axes:
            continue
        total = int(np.prod([sizes[a] for a in axes]))
        # Internal with_sharding_constraint supports uneven (padded)
        # sharding; jit argument shardings (strict=True) require exact
        # divisibility.
        ok = (dim_size % total == 0) if strict else (dim_size >= total)
        if total > 1 and ok:
            for a in axes:
                used.add(a)
            return tuple(axes) if len(axes) > 1 else axes[0]
    return None


def spec_for(logical_axes: Sequence[Optional[str]],
             shape: Sequence[int], mesh: Mesh, strict: bool = False) -> P:
    sizes = _mesh_axis_sizes(mesh)
    used: set = set()
    parts = [_resolve_axis(ax, d, sizes, used, strict)
             for ax, d in zip(logical_axes, shape)]
    return P(*parts)


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without a mesh)."""
    mesh = _ACTIVE["mesh"]
    if mesh is None or len(logical_axes) != x.ndim:
        return x
    if not _ACTIVE["seq_parallel"]:
        logical_axes = [None if a in ("seq", "kv_seq") else a
                        for a in logical_axes]
    spec = spec_for(logical_axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding (name + shape based)
# ---------------------------------------------------------------------------

# Patterns are matched against '/'-joined param paths.  Axis names refer to
# trailing dims; leading stack dims (layers) are never sharded.
_PARAM_RULES = [
    # embeddings: (vocab, d_model)
    (r"embed.*/table$", ("vocab", "embed_fsdp")),
    (r"lm_head/w$", ("embed_fsdp", "vocab")),
    # MoE expert tensors: (E, d_in, d_out)
    (r"(moe|ffn_moe).*/w_(up|gate)$", ("expert", "fsdp", None)),
    (r"(moe|ffn_moe).*/w_down$", ("expert", None, "fsdp")),
    (r"(moe|ffn_moe).*/router/w$", (None, None)),
    # generic 2-D projections: FSDP in, TP out
    (r"/(w_up|w_gate|wq|wk|wv|in_proj|x_proj)/w$", ("fsdp", "tp")),
    (r"/(w_down|wo|out_proj|dt_proj)/w$", ("tp", "fsdp")),
    (r"/w$", ("fsdp", "tp")),
    # biases / norms / vectors: shard like the out dim when large
    (r"/b$", ("tp",)),
    (r".*", ()),
]

_LOGICAL_PARAM_AXES = {
    "vocab": ("model",),
    "embed_fsdp": ("data",),
    "expert": ("model",),
    "fsdp": ("data",),
    "tp": ("model",),
}


def _param_spec(path: str, shape: Tuple[int, ...], sizes: Dict[str, int]) -> P:
    ndim = len(shape)
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            spec: list = [None] * ndim
            if not axes:
                return P(*spec)
            n = len(axes)
            if ndim < n:
                return P(*spec)
            used: set = set()
            offset = ndim - n          # leading dims = layer stacks
            for i, logical in enumerate(axes):
                if logical is None:
                    continue
                mesh_axes = _LOGICAL_PARAM_AXES.get(logical, ())
                for a in mesh_axes:
                    # params are jit arguments: exact divisibility required
                    if a in sizes and a not in used and sizes[a] > 1 \
                            and shape[offset + i] % sizes[a] == 0:
                        spec[offset + i] = a
                        used.add(a)
                        break
            return P(*spec)
    return P(*([None] * ndim))


def _flatten_with_paths(tree, prefix=""):
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(_flatten_with_paths(tree[k], f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.extend(_flatten_with_paths(v, f"{prefix}/{i}"))
    else:
        out.append((prefix, tree))
    return out


def param_pspecs(params_shapes, mesh: Mesh):
    """Pytree of PartitionSpec matching a pytree of arrays/ShapeDtypeStructs."""
    sizes = _mesh_axis_sizes(mesh)

    def build(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build(v, f"{prefix}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [build(v, f"{prefix}/{i}") for i, v in enumerate(tree)]
            return type(tree)(t)
        return _param_spec(prefix, tuple(tree.shape), sizes)

    return build(params_shapes)


def param_shardings(params_shapes, mesh: Mesh):
    specs = param_pspecs(params_shapes, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def input_pspec(shape: Tuple[int, ...], logical: Sequence[Optional[str]],
                mesh: Mesh) -> NamedSharding:
    # inputs are jit arguments: strict divisibility
    return NamedSharding(mesh, spec_for(logical, shape, mesh, strict=True))


# ---------------------------------------------------------------------------
# Decode-state (KV cache / SSM state) sharding — name + rank based
# ---------------------------------------------------------------------------

# Logical axes per cache leaf, selected by (path suffix, rank).  Leading
# stack dims (scan periods) are padded with None.
_STATE_RULES = [
    (r"attn/k$|attn/v$|cross_k$|cross_v$",
     ("batch", "kv_heads", "kv_seq", None)),
    (r"/ckv$", ("batch", "kv_seq", None)),
    (r"/krope$", ("batch", "kv_seq", None)),
    (r"ssm/conv$", ("batch", None, "mlp")),
    (r"ssm/state$", ("batch", "mlp", None)),
    (r"/wkv$", ("batch", "heads", None, None)),
    (r"/shift_t$|/shift_c$", ("batch", "embed")),
]


def _state_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
                seq_parallel: bool = True) -> P:
    for pat, logical in _STATE_RULES:
        if re.search(pat, path):
            n_lead = len(shape) - len(logical)
            if n_lead < 0:
                break
            axes = list(logical)
            if not seq_parallel:
                axes = [None if a == "kv_seq" else a for a in axes]
            full = [None] * n_lead + axes
            return spec_for(full, shape, mesh, strict=True)
    return P(*([None] * len(shape)))


def state_pspecs(state_shapes, mesh: Mesh, seq_parallel: bool = True):
    def build(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build(v, f"{prefix}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(build(v, f"{prefix}/{i}")
                              for i, v in enumerate(tree))
        return _state_spec(prefix, tuple(tree.shape), mesh, seq_parallel)

    return build(state_shapes)


def state_shardings(state_shapes, mesh: Mesh, seq_parallel: bool = True):
    specs = state_pspecs(state_shapes, mesh, seq_parallel)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
