"""Whole train step's share of the chip's bf16 peak: the FLOPs the step
needs (the configuration's ``train_step_flops``, ``harness.counts_for``:
forward and backward, causal attention, no rematerialisation) over the
device time of the jitted train step in the trace, per call."""
import trace_reduce


def read(ctx):
    sec, calls = trace_reduce.module_time(ctx.trace, r"jit_train_step")
    if not calls or not sec:
        return None
    t = ctx.traffic
    need = ctx.counts.train_step_flops(ctx.config, t["batch"], t["seq_len"])
    return 100.0 * need * calls / sec / ctx.peak["bf16_flops"]
