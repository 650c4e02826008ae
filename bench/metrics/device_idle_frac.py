"""device_idle_frac: 1 - (union of the device's op intervals) / (traced
window), mean over the cell's chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.trace.window_ns <= 0:
        return None
    return 1.0 - ctx.trace.mean("busy_ns") / ctx.trace.window_ns
