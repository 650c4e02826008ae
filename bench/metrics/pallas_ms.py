"""pallas_ms: device milliseconds per step in Pallas (Mosaic) kernels,
mean over the cell's chips."""


def read(ctx):
    if (ctx.trace is None or not ctx.steps
            or not ctx.trace.has_category("pallas")):
        return None
    return ctx.trace.mean("category_ns", "pallas") / 1e6 / ctx.steps
