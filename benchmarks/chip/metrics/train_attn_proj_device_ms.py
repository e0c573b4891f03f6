"""Device time of one call of the jitted train step spent under the
``attn_proj`` scope: the q/k/v/o projections, QKV bias, RoPE and head
transposes, forward, rematerialised and backward (``scopes.py``)."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "attn_proj")
    return None if t is None else 1e3 * t["s"]
