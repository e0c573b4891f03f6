"""Device time of one call of the jitted train step spent under the
``head`` scope: the vocabulary projection (the tied embedding here) and
the chunked cross-entropy's log-softmax and NLL, forward,
rematerialised and backward (``scopes.py``)."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "head")
    return None if t is None else 1e3 * t["s"]
