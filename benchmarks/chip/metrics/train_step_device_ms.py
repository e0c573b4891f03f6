"""Device time of one call of the jitted train step, from the trace."""
import trace_reduce


def read(ctx):
    sec, calls = trace_reduce.module_time(ctx.trace, r"jit_train_step")
    return 1e3 * sec / calls if calls else None
