"""Device time of one call of the jitted train step spent under the
``attn_core`` scope: scores, mask, online softmax and P.V (the
``chunked_attention`` scan), forward, rematerialised and backward
(``scopes.py``)."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "attn_core")
    return None if t is None else 1e3 * t["s"]
