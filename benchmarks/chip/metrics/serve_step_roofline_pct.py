"""The serve step's share of its roofline: for every call in the traced
window, the least time the chip needs (the larger of the FLOPs over the
bf16 peak and the bytes over the HBM bandwidth, from the configuration's
counts, ``harness.counts_for``: weights read once, the cache at each
slot's live length), summed, over the device time of the jitted serve
step."""
import trace_reduce


def calls(ctx):
    """(live lengths) of every dispatch: a prompt token is a call of one
    slot, a decode step a call of every active slot."""
    for kind, _, ctxs in ctx.rec.get("work", []):
        if kind == "admit":
            for n in ctxs:
                yield [n]
        else:
            yield ctxs


def read(ctx):
    sec, n = trace_reduce.module_time(ctx.trace, r"jit_serve_step")
    if not n or not sec:
        return None
    c, pk, counts = ctx.config, ctx.peak, ctx.counts
    need = sum(max(counts.decode_step_flops(c, x) / pk["bf16_flops"],
                   counts.decode_step_bytes(c, x) / pk["hbm_bytes_per_s"])
               for x in calls(ctx))
    per_call = need / max(sum(1 for _ in calls(ctx)), 1)
    return 100.0 * per_call * n / sec
