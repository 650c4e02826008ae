"""collective_ms: device milliseconds per step in collective ops
(all-to-all, collective-permute and the other cross-chip ops), mean over
the cell's chips."""


def read(ctx):
    if (ctx.trace is None or not ctx.steps
            or not ctx.trace.has_category("collective")):
        return None
    return ctx.trace.mean("category_ns", "collective") / 1e6 / ctx.steps
