"""Share of the FLOPs executed under the ``attn_core`` scope of the train
step that causal attention needs: 3 x ``flops.attention_pair_flops`` x
S(S+1)/2 a sequence, over the compiler's ``flops`` of every executed
``attn_core`` op per call (``scopes.py``).  It falls where all S^2 pairs
are computed, and again for each recomputation of the forward."""
import flops
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "attn_core")
    if not t or not t["flops"]:
        return None
    tr = ctx.traffic
    S = tr["seq_len"]
    need = 3.0 * flops.attention_pair_flops(ctx.config) * S * (S + 1) / 2 \
        * tr["batch"]
    return 100.0 * need / t["flops"]
