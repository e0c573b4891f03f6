"""Device time of one call of the jitted train step spent under the
``moe_experts`` scope inside ``ffn``: the grouped matmuls and SwiGLU of
the experts held, forward, rematerialised and backward
(``moe_scopes.py``)."""
import moe_scopes


def read(ctx):
    t = moe_scopes.train_nested(ctx, "moe_experts")
    return None if t is None else 1e3 * t
