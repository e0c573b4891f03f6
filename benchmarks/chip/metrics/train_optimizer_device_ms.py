"""Device time of one call of the jitted train step spent under the
``optimizer`` scope: global-norm clipping and the AdamW update of the
f32 parameters and moments (``scopes.py``)."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "optimizer")
    return None if t is None else 1e3 * t["s"]
