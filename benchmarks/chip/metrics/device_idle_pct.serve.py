"""Share of the traced serving window in which no op ran on the device:
1 - busy / window, from the profiler trace."""


def read(ctx):
    red = ctx.trace
    if not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
