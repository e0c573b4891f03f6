"""Share of the bf16 roofline reached under the ``attn_core`` scope of the
train step: the FLOPs causal attention needs (q.k and p.v for each pair
with key <= query, once, forward and backward: 3 x the configuration's
``attention_pair_flops`` (``harness.counts_for``) x S(S+1)/2 a sequence)
over the scope's device time per call, over the chip's bf16 peak.  FLOPs
bound it: the bytes attention must move (q, k, v, out and their
gradients) are about a thousandth of its FLOPs."""
import scopes


def read(ctx):
    t = scopes.train_scope(ctx, "attn_core")
    if not t or not t["s"]:
        return None
    tr = ctx.traffic
    S = tr["seq_len"]
    need = 3.0 * ctx.counts.attention_pair_flops(ctx.config) \
        * S * (S + 1) / 2 * tr["batch"]
    return 100.0 * need / t["s"] / ctx.peak["bf16_flops"]
