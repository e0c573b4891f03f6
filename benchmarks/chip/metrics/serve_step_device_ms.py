"""Device time of the jitted serve step per call, prompt dispatches
included, from the trace."""
import trace_reduce


def read(ctx):
    sec, calls = trace_reduce.module_time(ctx.trace, r"jit_serve_step")
    return 1e3 * sec / calls if calls else None
