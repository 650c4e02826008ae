"""collective_exposed_ms: device milliseconds per step in which a
collective runs and no other op runs on that chip, mean over the cell's
chips: the collective time that compute does not hide."""


def read(ctx):
    if (ctx.trace is None or not ctx.steps
            or not ctx.trace.has_category("collective")):
        return None
    return ctx.trace.mean("exposed_collective_ns") / 1e6 / ctx.steps
