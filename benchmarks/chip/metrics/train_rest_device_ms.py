"""Device time of one call of the jitted train step spent in ops under
none of the program's scopes: norms, the embedding and its scatter-add
gradient, residual adds, copies (``scopes.py``).  Nothing is read from
a program that names no scope."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, scopes.REST)
    return None if t is None else 1e3 * t["s"]
