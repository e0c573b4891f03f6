"""Share of the roofline reached under the ``moe_experts`` scope of the
train step: the least time the held experts' grouped matmuls need (the
larger of the configuration's ``held_expert_train_flops`` over the chip's
bf16 peak and its ``held_expert_train_bytes`` over the chip's bandwidth,
``harness.counts_for``; forward and backward at the expected held rows)
over the scope's device time per call.  FLOPs bound it at the cell's
size.  Nothing is read where the counts module has no held experts."""
import moe_scopes


def read(ctx):
    t = moe_scopes.train_nested(ctx, "moe_experts")
    flops = getattr(ctx.counts, "held_expert_train_flops", None)
    if not t or flops is None:
        return None
    tr = ctx.traffic
    B, S = tr["batch"], tr["seq_len"]
    least = max(flops(ctx.config, B, S) / ctx.peak["bf16_flops"],
                ctx.counts.held_expert_train_bytes(ctx.config, B, S)
                / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
