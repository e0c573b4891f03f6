"""Median time from a request's admission to its first token, on the host
clock: the server's token-by-token prefill and the first decode step."""
import numpy as np


def read(ctx):
    xs = ctx.rec.get("prefill_ms")
    return float(np.median(xs)) if xs else None
