"""Device time of one call of the jitted train step spent under the ``mtp``
scope: the multi-token-prediction depth whole (the next tokens' embedding,
the two norms and their projection, its decoder block with its router and
experts, and its pass of the head), forward, rematerialised and backward.

The program nests ``mtp`` around its own scopes (``repro/models/scopes.py``
``NESTED``), so ``scopes.py`` keeps counting the depth's ops under
``attn_core``, ``ffn``, ``head`` and the rest.  This reader puts every op
of the train step whose ``tf_op`` names ``mtp``, at any depth, under the
depth, from the same metadata that ``moe_scopes.py`` reads
(``scopes.read_metadata``, ``scopes._module_ops``).  A program without the
scope gives ``None``.
"""
import functools

import scopes
import trace_reduce

SCOPE = "mtp"


def in_depth(tf_op: str) -> bool:
    """Whether an op's ``tf_op`` names the depth's scope."""
    return SCOPE in scopes._WORD.findall(tf_op or "")


@functools.lru_cache(maxsize=4)
def _seconds(path, stamp):
    calls, ops_in = scopes._module_ops(trace_reduce.read_xplane(path),
                                       scopes.read_metadata(path),
                                       scopes.TRAIN_MODULE)
    sec = [d for _, m, d in ops_in if in_depth(str(m.get("tf_op", "")))]
    if not calls or not sec:
        return None
    return sum(sec) / calls


def read(ctx):
    path = scopes.cell_trace(ctx.cell.name)
    t = _seconds(path, scopes._stamp(path)) if path else None
    return None if t is None else 1e3 * t
