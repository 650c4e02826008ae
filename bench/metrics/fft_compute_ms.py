"""fft_compute_ms: device milliseconds per step in the local-FFT category
(every device op that is neither a collective nor a Pallas kernel),
mean over the cell's chips."""


def read(ctx):
    if ctx.trace is None or not ctx.steps or not ctx.trace.has_category("fft"):
        return None
    return ctx.trace.mean("category_ns", "fft") / 1e6 / ctx.steps
