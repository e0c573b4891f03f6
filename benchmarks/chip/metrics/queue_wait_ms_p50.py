"""Median wait of a request in the admission queue: admit time minus due
time, on the host clock, over the requests due in the window."""
import numpy as np


def read(ctx):
    xs = ctx.rec.get("queue_wait_ms")
    return float(np.median(xs)) if xs else None
