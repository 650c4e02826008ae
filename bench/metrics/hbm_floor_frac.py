"""hbm_floor_frac: the least HBM bytes of one step on one chip, computed
from the shapes by the configuration's ``least_hbm_bytes``, at the chip's
published HBM bandwidth, over the traced run's time per step.  It counts
the same work whatever implements it, so it cannot pass 1."""


def read(ctx):
    if (ctx.trace is None or ctx.peaks is None or not ctx.steps
            or ctx.trace.window_ns <= 0):
        return None
    floor_s = ctx.least_hbm_bytes / ctx.peaks["hbm_bytes_per_s"]
    return floor_s / (ctx.trace.window_ns / 1e9 / ctx.steps)
