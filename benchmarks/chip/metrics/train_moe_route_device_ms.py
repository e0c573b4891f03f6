"""Device time of one call of the jitted train step spent under the
``moe_route`` scope inside ``ffn``: the router over all experts, top-k,
the balance loss, the sort by expert, the dispatch and combine of the
rows, forward, rematerialised and backward (``moe_scopes.py``)."""
import moe_scopes


def read(ctx):
    t = moe_scopes.train_nested(ctx, "moe_route")
    return None if t is None else 1e3 * t
