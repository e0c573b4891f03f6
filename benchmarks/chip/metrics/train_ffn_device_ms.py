"""Device time of one call of the jitted train step spent under the
``ffn`` scope: the feed-forward (SwiGLU here), forward, rematerialised
and backward (``scopes.py``)."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "ffn")
    return None if t is None else 1e3 * t["s"]
